"""Device-resident sorted record gather + markdup flag patch.

The write half of the on-chip residency story.  The read side already
leaves each split's inflated payload in HBM (``RecordBatch.device_data``,
PR 4); until now ``write_part_fast`` still assembled every part on the
host — NumPy fancy-indexing gather, host ``patch_flags``, host CRC32 —
and shipped the *uncompressed* stream h2d into the deflate lanes.  This
module assembles the part straight from the resident payloads: output
byte p of the permuted record stream reads
``stream[src0[r] + (p - dst0[r])]`` for its covering record r, and
duplicate records get ``FLAG_DUPLICATE`` ORed into their two flag bytes
(body offset 14 → bytes 18/19 past the size word) — no host bounce of the
payload.

Formulation notes (why this kernel-family member is an XLA program):
the source offset ``src0[r] - dst0[r]``
is constant along a record, so each output tile scatters the offset's
*change* at every record start and one ``cumsum`` spreads it over the
tile: per output byte one cumsum step and one gather from the stream — no
per-byte binary search over the records.  Launches are chunked in fixed
output tiles with pow2-bucketed record columns so distinct jit signatures
stay few.

Only O(records) int32 columns ride h2d (≈12 bytes/record against the
~170-byte records they describe); the gathered stream itself is born in
HBM and feeds ``deflate_lanes`` device-to-device.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

#: SAM FLAG_DUPLICATE — the only patch the dedup write stage applies.
_FLAG_DUPLICATE = 0x400

#: Output positions per launch (gather elements stay far under the
#: XLA:TPU 2^24 index-precision cap; see ops/flate.py `_MAX_LAUNCH_ELEMS`).
_CHUNK = 1 << 22


def _pow2_at_least(n: int, lo: int) -> int:
    v = lo
    while v < n:
        v *= 2
    return v


@functools.partial(jax.jit, static_argnums=(5,))
def _gather_tile(stream, pos, step, carry, b0, chunk: int, total):
    """Output bytes ``[b0, b0 + chunk)`` of the gathered stream: the
    source offset starts at ``carry`` and changes by ``step[i]`` at tile
    position ``pos[i]`` (a record start; padding rows sit at ``chunk``
    and drop)."""
    S = stream.shape[0]
    delta = jnp.zeros(chunk, jnp.int32).at[pos].add(step, mode="drop")
    p = b0 + jnp.arange(chunk, dtype=jnp.int32)
    src = p + carry + jnp.cumsum(delta)
    out = stream[jnp.clip(src, 0, S - 1)]
    return jnp.where(p < total, out, jnp.uint8(0))


@jax.jit
def _patch_flags(flat, pos, lo, hi):
    """OR the duplicate flag bits into the two flag bytes of each
    duplicate record (``pos`` = its size word's output offset)."""
    flat = flat.at[pos + 18].set(flat[pos + 18] | lo)
    return flat.at[pos + 19].set(flat[pos + 19] | hi)


def gather_stream_device(
    stream,
    src_starts: np.ndarray,
    lens: np.ndarray,
    dup_mask: Optional[np.ndarray] = None,
    bits: int = _FLAG_DUPLICATE,
    chunk: int = _CHUNK,
) -> Tuple[jax.Array, int]:
    """Assemble a permuted record stream in HBM from a resident payload.

    ``stream``: device uint8 (the flat resident payload bytes);
    ``src_starts``: int64 [R] position of each output record's size word
    in ``stream``, already in output (sorted) order; ``lens``: int64 [R]
    total bytes per record (size word + body); ``dup_mask``: optional
    bool [R] — rows to patch with ``bits`` (default ``FLAG_DUPLICATE``)
    at flag-byte offsets 18/19, the device ``io.bam.patch_flags``.

    Returns ``(device uint8 [total], total)``.  Raises ``ValueError``
    when the geometry leaves the int32 gather domain (callers tier down
    to the host gather).
    """
    from ...utils.tracing import count_h2d

    src_starts = np.asarray(src_starts, dtype=np.int64)
    lens = np.asarray(lens, dtype=np.int64)
    R = len(src_starts)
    if R == 0:
        return jnp.zeros((0,), jnp.uint8), 0
    dst_end = np.cumsum(lens)
    total = int(dst_end[-1])
    if total >= 2**31 or int((src_starts + lens).max()) >= 2**31:
        raise ValueError("gather geometry outside the int32 domain")
    dst_start = dst_end - lens
    off = src_starts - dst_start  # source offset, constant per record
    step = np.diff(off, prepend=0)
    dev = jnp.asarray(stream)
    parts = []
    h2d = 0
    for b0 in range(0, total, chunk):
        r0, r1 = np.searchsorted(dst_start, [b0, b0 + chunk])
        # The offset in force at b0 belongs to the last record starting
        # before the tile; records starting inside it step it.
        carry = int(off[r0 - 1]) if r0 else 0
        Rp = _pow2_at_least(int(r1 - r0), 256)
        pos = np.full(Rp, chunk, dtype=np.int32)
        stp = np.zeros(Rp, dtype=np.int32)
        pos[: r1 - r0] = dst_start[r0:r1] - b0
        stp[: r1 - r0] = step[r0:r1]
        h2d += pos.nbytes + stp.nbytes
        parts.append(
            _gather_tile(
                dev, jnp.asarray(pos), jnp.asarray(stp), jnp.int32(carry),
                jnp.int32(b0), chunk, jnp.int32(total),
            )
        )
    flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    flat = flat[:total]
    if dup_mask is not None and np.any(dup_mask):
        rows = np.nonzero(np.asarray(dup_mask))[0]
        pos_d = dst_start[rows].astype(np.int32)
        h2d += pos_d.nbytes
        flat = _patch_flags(
            flat, jnp.asarray(pos_d), jnp.uint8(bits & 0xFF),
            jnp.uint8((bits >> 8) & 0xFF),
        )
    count_h2d(h2d, "write_cols")
    return flat, total


# --------------------------------------------------------------------------
# Bench probe (bench.py reports device_write_MBps per round on TPU).
# --------------------------------------------------------------------------


def bench_write_marginal(
    n_small: int = 1 << 20, n_big: int = 4 << 20
) -> dict:
    """Marginal throughput of the device write front-end (sorted gather +
    flag patch + CRC32) via a two-point fit — the same launch-free
    protocol as ``inflate_probe.bench_marginal``: one resident stream, two
    output sizes; the slope is the per-byte cost, the intercept absorbs
    the launch.  The deflate stage is excluded (it has its own
    ``device_deflate_MBps`` probe)."""
    import time

    from .crc32 import crc32_device

    rng = np.random.default_rng(3)
    rec_len = 168
    n_rec = n_big // rec_len + 1
    stream = jnp.asarray(
        rng.integers(0, 256, n_rec * rec_len, dtype=np.uint8)
    )
    perm = rng.permutation(n_rec)
    src = (perm * rec_len).astype(np.int64)
    lens = np.full(n_rec, rec_len, dtype=np.int64)
    dup = rng.random(n_rec) < 0.1

    def timed(nbytes: int) -> float:
        k = nbytes // rec_len
        offs = np.arange(0, k * rec_len, 57088, dtype=np.int64)
        mlens = np.minimum(57088, k * rec_len - offs)

        def once():
            out, total = gather_stream_device(
                stream, src[:k], lens[:k], dup_mask=dup[:k]
            )
            jax.block_until_ready(crc32_device(out, offs, mlens))

        once()  # warm the jit caches
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            once()
            ts.append(time.perf_counter() - t0)
        return min(ts)

    dt_s = timed(n_small)
    dt_b = timed(n_big)
    per_byte = (dt_b - dt_s) / (n_big - n_small)
    fixed = dt_s - per_byte * n_small
    bytes_per_s = 1.0 / per_byte if per_byte > 0 else float("inf")
    return {
        "fixed_ms": fixed * 1e3,
        "bytes_per_s": bytes_per_s,
        "projected_mb_s": bytes_per_s / 1e6,
        "t_small_ms": dt_s * 1e3,
        "t_big_ms": dt_b * 1e3,
    }
