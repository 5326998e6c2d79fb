"""DeviceStream: one fused device graph with double-buffered split pipelining.

ROADMAP open item #1.  The built device path — the lockstep-lane codec
tiers (PR 1/2/4), the device parse (PR 4), the resident part writes
(PR 5), the serve arena/batcher (PR 6) — historically stitched through
host Python between every stage, with the gates, residency handles,
deadline checks and ledger calls scattered across ``ops/flate.py``,
``io/bam.py``, ``pipeline.py`` and ``serve/batching.py``.  This module
is the consolidation: a :class:`DeviceStream` owns, in one place,

- the **codec tier policy** (:class:`StreamPolicy`): the inflate-lanes /
  deflate-lanes / device-write gates resolved once per stream by the
  platform rule — every tier on when the backend is a TPU, off
  otherwise, with the ``HBAM_*`` env vars and ``hadoopbam.*`` conf keys
  as explicit forces;
- the **residency handle**: every attach/transfer/release of a
  device-resident buffer a stream client makes goes through the
  :data:`~hadoop_bam_tpu.utils.hbm.LEDGER` via this object, so the
  PR 11 leak/double-copy instruments see one consistent holder story;
- the **deadline check** (:meth:`check_deadline`): the request's
  end-to-end budget is re-checked between pipeline stages — a split
  never uploads, parses or encodes on a spent budget;
- the **transfer ledger**: h2d/d2h crossings ride the existing
  ``utils.tracing.count_h2d``/``count_d2h`` seams of the ops the stream
  drives, so the round artifacts keep one source of PCIe truth.

The **double-buffered drive** (:meth:`read_splits`) streams splits
through a read-ahead pool ``depth`` deep (``hadoopbam.read.depth`` conf
key → ``HBAM_READ_DEPTH`` env → 2): split *k+1*'s file read, h2d upload
and device inflate/parse kernels dispatch while split *k*'s host-side
batch assembly runs, and the part-write d2h rides the lazily-awaited
async fetches (``pipeline._LazyPermFetch``, the executor's concurrent
part encoders).  Between stages the stream **donates** buffers
(``jax.jit(..., donate_argnums=…)``) so HBM never holds two copies of a
split:

- *inflate→parse*: the split window is donated into the chain kernel's
  padded parse stream (:meth:`parse_split`) when the write path will not
  gather from it;
- *windows→write stream*: the per-split windows are donated into the
  flat write-stream concat (:func:`donating_concat`, used by
  ``io.bam.ChunkedRecords.from_batches``);
- *gather→deflate*: the gathered part column is donated into its final
  reader, the on-chip CRC launch
  (``ops.flate.bgzf_compress_device(donate_input=True)``).

Backends without donation support (the CPU/interpret CI) run the same
code minus the aliasing (``utils.backend.donation_supported``); the
PR 11 double-copy detector is the regression guard either way.

Disarmed contract: with every device tier off, a DeviceStream is a plain
read-ahead pool — zero ``device_stream.*`` counters move and the output
is byte-identical (asserted in tests/test_device_stream.py).
"""

from __future__ import annotations

import functools
import os
from typing import Iterator, Optional, Sequence

import numpy as np

from .utils.hbm import LEDGER
from .utils.tracing import METRICS, span, trace_ctx

#: Read-ahead depth when neither the argument, the conf key nor the env
#: var says otherwise (measured neutral-to-positive even on the 1-core
#: bench host — BENCH_NOTES.md).
DEFAULT_DEPTH = 2


def resolve_depth(conf=None, depth: Optional[int] = None) -> int:
    """The split-pipelining depth: explicit argument →
    ``hadoopbam.read.depth`` conf key → ``HBAM_READ_DEPTH`` env var →
    :data:`DEFAULT_DEPTH`.  Malformed overrides keep the default; the
    floor is 1 (no read-ahead)."""
    if depth is not None:
        return max(1, int(depth))
    if conf is not None:
        from .conf import READ_DEPTH

        v = conf.get_int(READ_DEPTH, 0)
        if v > 0:
            return v
    env = os.environ.get("HBAM_READ_DEPTH")
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            return DEFAULT_DEPTH
    return DEFAULT_DEPTH


class StreamPolicy:
    """The codec tier gates, resolved once per stream by the platform
    rule (``ops.flate._tier_enabled``): on a TPU backend every device
    tier is on unless its env var or conf key forces it off."""

    def __init__(
        self,
        inflate_lanes: bool,
        deflate_lanes: bool,
        device_write: bool,
        depth: int,
        use_rans_lanes: bool = False,
        use_bcf_chain: bool = False,
    ) -> None:
        self.inflate_lanes = inflate_lanes
        self.deflate_lanes = deflate_lanes
        self.device_write = device_write
        self.use_rans_lanes = use_rans_lanes
        self.use_bcf_chain = use_bcf_chain
        self.depth = depth

    @property
    def armed(self) -> bool:
        return (
            self.inflate_lanes
            or self.deflate_lanes
            or self.device_write
            or self.use_rans_lanes
            or self.use_bcf_chain
        )

    @classmethod
    def resolve(cls, conf=None, depth: Optional[int] = None) -> "StreamPolicy":
        from .ops import flate

        return cls(
            inflate_lanes=flate.lanes_tier_enabled(conf),
            deflate_lanes=flate.deflate_lanes_tier_enabled(conf),
            device_write=flate.device_write_enabled(conf),
            depth=resolve_depth(conf, depth),
            use_rans_lanes=flate.rans_lanes_tier_enabled(conf),
            use_bcf_chain=flate.bcf_chain_tier_enabled(conf),
        )


@functools.lru_cache(maxsize=None)
def _slice_pad_fn(n_bytes: int, pad_len: int, donate: bool):
    """Jitted slice+pad of a split window to the chain kernel's chunk
    geometry, optionally donating the window — the inflate→parse seam.
    Cached per (length, padding) pair: the same shapes the eager
    ``jnp.pad(dd[s0:s1], …)`` it replaces compiled per call anyway."""
    import jax

    def f(d, s0):
        import jax.numpy as jnp

        sl = jax.lax.dynamic_slice_in_dim(d, s0, n_bytes)
        return jnp.pad(sl, (0, pad_len - n_bytes))

    return jax.jit(f, donate_argnums=(0,) if donate else ())


@functools.lru_cache(maxsize=None)
def _concat_fn(n_parts: int, donate: bool):
    """Jitted device-to-device concat of per-split windows into the flat
    write stream, donors donated — the windows→write-stream seam."""
    import jax

    def f(*xs):
        import jax.numpy as jnp

        return jnp.concatenate(xs)

    return jax.jit(
        f, donate_argnums=tuple(range(n_parts)) if donate else ()
    )


def donating_concat(parts: Sequence):
    """Concatenate device-resident windows into one flat stream,
    donating the donors when the backend supports it, so HBM holds the
    windows *or* the flat stream — not both — during the write-phase
    setup (the double-copy window ``ChunkedRecords.from_batches`` used
    to open physically even though the ledger adopt closed it
    logically).  Ledger bookkeeping stays the caller's (``adopt``)."""
    from .utils.backend import donation_supported

    return _concat_fn(len(parts), donation_supported())(*parts)


class DeviceStream:
    """One job's fused device pipeline: tier policy + residency +
    deadline + transfer accounting, driving the split stream
    double-buffered.

    Clients: ``pipeline.sort_bam`` (the read drive, the parse seam, the
    part encodes), ``io.bam.read_split``/``write_part_fast`` (codec tier
    + residency attach), and the serve daemon's ``HbmArena`` and
    ``LaneBatcher`` (the same decode seam and residency story instead of
    parallel implementations).  A stream is cheap to construct — the
    gates resolve from env/conf/the platform — so one per job (or one per
    daemon) is the intended shape."""

    def __init__(
        self,
        conf=None,
        deadline=None,
        depth: Optional[int] = None,
        name: str = "device_stream",
    ) -> None:
        self.conf = conf
        self.deadline = deadline
        self.name = name
        self.policy = StreamPolicy.resolve(conf, depth)
        self.depth = self.policy.depth

    # -- shared plumbing ----------------------------------------------------

    @property
    def armed(self) -> bool:
        """Any device tier live?  Disarmed streams must move zero
        ``device_stream.*`` counters (the disarmed contract)."""
        return self.policy.armed

    def _count(self, suffix: str, n: int = 1) -> None:
        METRICS.count(f"device_stream.{suffix}", n)

    def check_deadline(self, seam: str) -> None:
        """Between-stage deadline check: raises ``DeadlineExceeded``
        instead of spending device time on an expired request.  Costs
        one ``is None`` branch in batch mode."""
        if self.deadline is not None:
            self.deadline.check(seam)

    # -- the residency handle (the ledger, in one place) --------------------

    def register(self, obj, kind: str, holder: str, **kw):
        return LEDGER.register(obj, kind, holder, **kw)

    def transfer(self, obj, holder: str, kind: Optional[str] = None):
        return LEDGER.transfer(obj, holder, kind=kind)

    def adopt(self, obj, kind: str, holder: str, donors=(), **kw):
        return LEDGER.adopt(obj, kind, holder, donors=donors, **kw)

    def release(self, obj) -> bool:
        return LEDGER.release(obj)

    def attach_window(self, dev, holder: str = "bam.split_window"):
        """The inflate tier left a split window in HBM: the stream hands
        ownership to the reader's batch (counted, ledgered)."""
        if dev is None:
            return None
        self._count("windows")
        return LEDGER.transfer(dev, holder)

    @staticmethod
    def release_batch(b) -> None:
        """Give a batch's HBM-resident window back through the ledger
        and drop the reference (the one release helper every drop site
        shares — ``pipeline._release_split_residency`` delegates here)."""
        dd = getattr(b, "device_data", None)
        if dd is not None:
            LEDGER.release(dd)
        b.device_data = None

    # -- the codec seam (split readers + the serve lane batcher) ------------

    def decode_members(
        self,
        data,
        coffsets,
        csizes,
        usizes,
        return_device: bool = False,
        threads: Optional[int] = None,
        on_error: str = "raise",
    ):
        """Decode a batch of BGZF members through the stream's tier
        policy — the shared seam behind ``io.bam.read_virtual_range``'s
        window inflate and the serve ``LaneBatcher``'s coalesced
        launches.  Contract of ``native.inflate_blocks``: ``(out,
        out_offsets)``, plus the device-resident window as a third value
        when ``return_device``.

        ``on_error="host"`` tiers a device launch that exhausted HBM
        down to the native codec for the whole call (counting
        ``bam.device_inflate_fallback`` and ``bam.oom_tierdown`` — the
        read path's policy); any other error propagates.  ``"raise"``
        propagates, which is what the serve OOM ladder needs (evict →
        retry → per-request tier-down happens a layer up)."""
        co = np.asarray(coffsets, dtype=np.int64)
        cs = np.asarray(csizes, dtype=np.int32)
        us = np.asarray(usizes, dtype=np.int32)
        if self.policy.inflate_lanes:
            from .ops import flate

            try:
                self._count("decodes")
                if return_device:
                    out, offs, dev = flate.inflate_blocks_device(
                        data, co, cs, us, return_device=True
                    )
                    return out, offs, dev
                return flate.inflate_blocks_device(data, co, cs, us)
            except Exception as e:
                from .utils.backend import is_resource_exhausted

                if on_error != "host" or not is_resource_exhausted(e):
                    raise
                METRICS.count("bam.device_inflate_fallback", 1)
                METRICS.count("bam.oom_tierdown", 1)
        from . import native

        out, offs = native.inflate_blocks(data, co, cs, us, threads=threads)
        if return_device:
            return out, offs, None
        return out, offs

    def decompress_cram_blocks(self, blocks, errors: str = "strict"):
        """Decode a batch of CRAM compressed blocks ``(method, payload,
        raw_size)`` through the stream's rANS tier policy — the third
        codec family's seam, behind ``spec.cram.decode_container``.  An
        armed stream routes rANS 4x8 blocks through the lockstep lanes
        (per-slice tier-down to the NumPy host decoder, counted under
        ``cram.rans.*``); a disarmed stream is the plain host batch and
        moves zero ``device_stream.*`` / ``cram.rans.*`` counters."""
        from .spec import cram_codecs

        if self.policy.use_rans_lanes:
            self._count("cram_decodes")
        return cram_codecs.decompress_batch(
            blocks,
            errors=errors,
            conf=self.conf,
            use_lanes=self.policy.use_rans_lanes,
        )

    def walk_bcf_records(self, payload, start: int, limit: int):
        """Walk a BCF record chain through the stream's tier policy — the
        fourth codec family's seam, behind ``io.bcf.read_split``.  An
        armed stream runs the device chain kernel
        (``ops.pallas.bcf_chain``) with per-window tier-down to the
        bit-exact NumPy walk; a disarmed stream returns ``None`` and the
        caller keeps the pre-existing host path byte-for-byte (the
        disarmed contract: zero ``device_stream.*``/``bcf.*`` counters).

        Returns ``(cols, count, ok, tier)`` from
        :func:`~hadoop_bam_tpu.ops.pallas.bcf_chain.walk_chain`, or
        ``None`` when the tier is off."""
        if not self.policy.use_bcf_chain:
            return None
        from .ops.pallas.bcf_chain import walk_chain

        return walk_chain(payload, start, limit)

    def deflate_stream(
        self, payload, level: int = 1, block_payload: Optional[int] = None
    ) -> bytes:
        """Compress a host byte stream into back-to-back BGZF members
        (no terminator) through the stream's deflate tier policy — the
        mesh shuffle's sender seam.  A lanes-armed stream rides
        ``deflate_blocks_device`` (per-member host-zlib tier-down as
        everywhere else, including the forced-tier-down fault seam); an
        unarmed stream uses the native host codec directly — real
        compression either way, and the member blocking (a cut every
        ``block_payload`` bytes) is identical, so the caller's member
        table math holds across tiers."""
        if self.policy.deflate_lanes:
            from .ops import flate

            self._count("deflates")
            return flate.deflate_blocks_device(
                np.asarray(payload),
                level=level,
                block_payload=block_payload,
                use_lanes=True,
                conf=self.conf,
            )
        from . import native

        kw = {} if block_payload is None else {"block_payload": block_payload}
        return native.deflate_blocks(payload, level=level, **kw)

    # -- the double-buffered split drive ------------------------------------

    def read_splits(
        self,
        fmt,
        splits,
        fields=None,
        depth: Optional[int] = None,
        with_keys: bool = True,
        errors: Optional[str] = None,
    ) -> Iterator:
        """Yield decoded split batches in order, double-buffered: a
        read-ahead pool ``depth`` deep keeps the next splits' file reads,
        h2d uploads and device inflate kernels in flight while the
        caller processes the current one.  The file read and the native
        inflate release the GIL, and the device tiers dispatch
        asynchronously, so on a lanes-armed stream split *k+1*'s upload
        rides under split *k*'s host-side work — the h2d leg of the
        double buffer (the d2h leg is the lazily-awaited perm fetch and
        the executor's concurrent part encodes).

        The chosen depth is published as the ``pipeline.read_depth``
        gauge (surfaced by the run manifest).  The deadline is checked
        once per split *between* stages — before the result wait — so an
        expired request stops at a stage boundary instead of mid-kernel.

        Under ``errors="salvage"`` a split whose read fails outright
        degrades to an *empty batch* with a ``salvage.splits_failed``
        counter instead of killing the job (yield order is preserved —
        the double-buffer ordering drills pin this)."""
        d = max(1, int(depth)) if depth is not None else self.depth
        METRICS.set_gauge("pipeline.read_depth", d)

        def read_one(si, s):
            # trace_ctx tags every stage event this split's read/inflate/
            # parse/key chain emits (in whichever pool thread it runs)
            # with the split index — the stall reducer's per-item
            # attribution.
            with trace_ctx(split=si), span(
                "pipeline.stage.read_split", category="item"
            ):
                try:
                    return fmt.read_split(
                        s,
                        fields=fields,
                        with_keys=with_keys,
                        errors=errors,
                        stream=self,
                    )
                except Exception:
                    if errors != "salvage":
                        raise
                    METRICS.count("salvage.splits_failed", 1)
                    from .io.bam import RecordBatch, _empty_soa

                    return RecordBatch(
                        soa=_empty_soa(fields),
                        data=np.empty(0, np.uint8),
                        keys=np.empty(0, np.int64),
                    )

        if d <= 1 or len(splits) <= 1:
            for si, s in enumerate(splits):
                self.check_deadline("stream_read")
                yield read_one(si, s)
            return
        from concurrent.futures import ThreadPoolExecutor

        pool = ThreadPoolExecutor(max_workers=d)
        futs = [
            pool.submit(read_one, si, s)
            for si, s in enumerate(splits[: d + 1])
        ]
        nxt = d + 1
        try:
            for i in range(len(splits)):
                # Stage boundary: an expired deadline stops here, before
                # blocking on (or dispatching more) device work.
                self.check_deadline("stream_read")
                b = futs[i].result()
                # Drop the Future (and with it the decoded batch it
                # retains) so only ~depth+1 batches are ever alive: the
                # external-sort path counts on this generator being
                # O(depth), not O(file).
                futs[i] = None
                if nxt < len(splits):
                    futs.append(pool.submit(read_one, nxt, splits[nxt]))
                    nxt += 1
                yield b
                del b
        finally:
            # On a decode error (or the consumer abandoning the
            # generator), don't block on — or keep paying for — reads
            # nobody will use.
            for f in futs:
                if f is not None:
                    f.cancel()
            pool.shutdown(wait=False, cancel_futures=True)

    # -- the inflate→parse seam ---------------------------------------------

    def parse_split(self, b, keep_residency: bool = False):
        """Upload (or donate) one split's record stream and launch the
        on-chip parse.

        Returns ``(hi, lo, unmapped, meta)`` device arrays (``meta`` =
        ``[count, ok, n_unmapped]`` int32), sliced to the host-known
        record count; ``None`` for an empty split; ``False`` when the
        stream is outside the kernel's int32 domain (caller falls back
        to host keys).  Everything is dispatched asynchronously — the
        chip walks the chain and builds keys while the host inflates the
        next split.

        When the split carries HBM residency, the window is sliced+
        padded on device (no h2d at all); unless ``keep_residency`` (the
        device write path still needs the window for its part gathers),
        the window is *donated* into the padded parse stream — the
        inflate→parse donation seam — so HBM never holds the window and
        the parse stream at once, and the ledger records the handoff as
        an adopt (donor closed, successor registered)."""
        from .ops.decode import keys_from_stream_device
        from .ops.pallas.chain import CHUNK

        import jax.numpy as jnp

        n_i = b.n_records
        if n_i == 0:
            return None
        self.check_deadline("stream_parse")
        rec_off = b.soa["rec_off"]
        rec_len = b.soa["rec_len"]
        # The batch window may hold bytes before the first record (split
        # vstart inside a block) and after the last (spill margin): slice
        # the exact back-to-back record stream, pre-padded to the chain
        # kernel's chunk geometry so only a handful of shapes compile.
        s0 = int(rec_off[0]) - 4
        s1 = int(rec_off[-1] + rec_len[-1])
        n_bytes = s1 - s0
        if n_bytes > 2**31 - CHUNK:
            # Past the chain kernel's int32 offset domain (only reachable
            # with a multi-GiB split_size): host keys for the whole job.
            return False
        n_chunks = max(1, -(-n_bytes // CHUNK))
        pad_len = n_chunks * CHUNK + 256 * 4
        dd = getattr(b, "device_data", None)
        if dd is not None:
            # On-chip output residency: the split's inflated bytes are
            # already in HBM (left there by the lockstep-lane inflate
            # tier) — slice+pad on device and skip the h2d entirely.
            if not keep_residency:
                from .utils.backend import donation_supported

                padded = _slice_pad_fn(
                    n_bytes, pad_len, donation_supported()
                )(dd, s0)
                # Ledger: the parse stream succeeds the window (donor
                # closed, successor registered); its own residency ends
                # when the chain kernel's outputs are all that remain.
                padded = LEDGER.adopt(
                    padded,
                    kind="parse_stream",
                    holder=f"{self.name}.parse",
                    donors=[dd],
                    nbytes=pad_len,
                )
                b.device_data = None
            else:
                padded = jnp.pad(dd[s0:s1], (0, pad_len - n_bytes))
            METRICS.count("sort_bam.device_parse_residency", 1)
        else:
            padded = np.zeros(pad_len, dtype=np.uint8)
            padded[:n_bytes] = b.data[s0:s1]
            from .utils.tracing import count_h2d

            count_h2d(padded.nbytes, "parse_stream")
        hi, lo, unm, count, ok = keys_from_stream_device(padded, n_bytes)
        self._count("parses")
        if dd is not None and not keep_residency:
            # The chain kernel's outputs are dispatched; the parse
            # stream's explicit residency ends here (jax frees the
            # buffer when the kernel completes).
            LEDGER.release(padded)
        meta = jnp.stack(
            [
                count.astype(jnp.int32),
                ok.astype(jnp.int32),
                jnp.sum(unm).astype(jnp.int32),
            ]
        )
        return hi[:n_i], lo[:n_i], unm[:n_i], meta

    # -- the gather→deflate seam --------------------------------------------

    def encode_part(
        self,
        batch,
        order: Optional[np.ndarray],
        dup_mask: Optional[np.ndarray],
        level: int,
    ) -> Optional[bytes]:
        """The device-resident part assembly: sorted gather + markdup
        flag patch on chip (``ops.pallas.gather_stream``), per-member
        CRC32 on chip (``ops.pallas.crc32``), deflate lanes fed
        device-to-device — the only d2h traffic is the compressed part
        blob (+ CRC column).  The gathered column is donated into its
        final reader, the CRC launch (the gather→deflate donation seam),
        so on donation-capable backends the part's uncompressed bytes
        free as the encode dispatches.

        Returns the part blob (always lanes-blocked at
        ``DEV_LZ_PAYLOAD``), or ``None`` to tier down to the host gather
        path; every tier-down records its reason
        (``bam.device_write_tierdown.{no_residency,size}`` /
        ``bam.device_write_fallback``) so a silently-dead path shows up
        in the round artifacts."""
        from .io.bam import ChunkedRecords
        from .ops import flate as _flate

        if isinstance(batch, ChunkedRecords):
            if batch.device_flat is None:
                METRICS.count("bam.device_write_tierdown.no_residency", 1)
                return None
            stream_dev = batch.device_flat
            base = batch.chunk_base[
                np.asarray(batch.chunk_id, dtype=np.int64)
            ]
            src = base + np.asarray(batch.soa["rec_off"], np.int64) - 4
        else:
            if getattr(batch, "device_data", None) is None:
                METRICS.count("bam.device_write_tierdown.no_residency", 1)
                return None
            stream_dev = batch.device_data
            src = np.asarray(batch.soa["rec_off"], np.int64) - 4
        lens = np.asarray(batch.soa["rec_len"], np.int64) + 4
        if order is not None:
            src = src[order]
            lens = lens[order]
        if len(src) == 0:
            return None  # empty part: the host path writes its canonical form
        total = int(lens.sum())
        if (
            total + _flate.DEV_LZ_PAYLOAD >= 2**31 - 8
            or int((src + lens).max()) >= 2**31
        ):
            # Past the int32 domain of the device gather and CRC.
            METRICS.count("bam.device_write_tierdown.size", 1)
            return None
        self.check_deadline("stream_encode")
        dm = None
        if dup_mask is not None:
            dm = dup_mask[order] if order is not None else dup_mask
            if not dm.any():
                dm = None
        gathered = None
        try:
            from .ops.pallas.gather_stream import gather_stream_device

            with span("bam.write.gather"):
                gathered, _ = gather_stream_device(
                    stream_dev, src, lens, dup_mask=dm
                )
            # The permuted gather column is a second resident stream for
            # the duration of the deflate — ledgered so the HBM track
            # shows the write-phase bump and a dropped release would be
            # named.  Its buffer is donated into the CRC launch below.
            LEDGER.register(
                gathered, kind="write_gather", holder="bam.device_write"
            )
            blob = _flate.deflate_blocks_device(
                None,
                level=level,
                block_payload=_flate.DEV_LZ_PAYLOAD,
                use_lanes=True,
                conf=self.conf,
                device_input=gathered,
                donate_input=True,
            )
        except Exception as e:
            from .utils.backend import is_resource_exhausted

            # HBM exhaustion tiers the part down to the bit-correct host
            # gather; a compile or lowering error propagates.
            if not is_resource_exhausted(e):
                raise
            METRICS.count("bam.device_write_fallback", 1)
            return None
        finally:
            if gathered is not None:
                LEDGER.release(gathered)
        METRICS.count("bam.device_write_parts", 1)
        return blob
