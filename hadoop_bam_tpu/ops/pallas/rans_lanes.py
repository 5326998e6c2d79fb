"""Lockstep-lane Pallas decoder for rANS 4x8 (CRAM 3.0), HBM-streaming.

The third codec family on the inflate-lanes engine pattern: up to 128
compressed CRAM block payloads ride the 128 vector lanes of one kernel,
each advancing its own 4-state rANS machine in lockstep waves.  rANS is
the lockstep-friendly entropy coder — a fixed 4-way interleaved state
machine with byte-granular renormalization and no bit-serial Huffman —
so unlike DEFLATE there is no per-lane table build on chip: the order-0
/ order-1 frequency tables are tiny and parse host-side
(``spec.cram_codecs.parse_rans_plan``) into dense per-lane context banks.

Wave model (shared with the NumPy host tier in ``spec/cram_codecs.py``
— see the plan/wave notes there): global wave ``t`` decodes one byte per
lane with state ``j = t&3`` through the four quarters and ``j = 3`` in
the order-1 remainder tail; output lands in wave order and the host
de-interleaves order-1 quarters after download
(``cram_codecs.rans_deinterleave``).  Per the engine house style, every
per-lane lookup is a dense iota-compare column reduction, never a
gather:

- "my state / my last symbol" are one-hot row selects over the packed
  ``st`` register file;
- "which symbol owns slot ``m``" is a count of ``C <= m`` rows inside
  the lane's active context slab of the cumulative-frequency bank (the
  searchsorted-as-reduction idiom);
- "one renorm byte at my cursor" is a one-hot word select over the
  transposed stream bank, at most two per wave (the encoder invariants
  bound it; a stream needing more is corrupt and flips ``ok``).

**Streaming geometry**: the kernel grids over fixed-size output chunks
(``chunk_bytes`` per lane per grid step, 4 wave-bytes packed per int32
word); finished tiles stream to the HBM-backed output while the state
file persists in VMEM scratch.  Per-slice ``[n_out, ok]`` meta tiers a
slice that trips a size/VMEM/context/format gate — or that violates the
stream invariants mid-decode — down to the host tiers *per slice, never
per launch*.

Oracle: ``spec.cram_codecs.rans_decode_py`` (the original per-byte
Python decoder) via tests/test_rans_lanes.py; tests run the kernel in
interpret mode on CPU and compare byte-for-byte.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils.backend import pallas_interpret

from ...spec import cram_codecs as _cc

LANES = 128

_RANS_L = 1 << 23
_TF_SHIFT = 12
_TOTFREQ = 1 << _TF_SHIFT

#: VMEM budget for one launch (streams + context banks + tile + state).
_VMEM_BUDGET_BYTES = 14 << 20

#: Per-slice output-size cap; past it the wrapper declines without
#: launching (tier-down reason "size").
_MAX_OSIZE = 1 << 20

#: Dense context-slab cap per slice (order-1 tables); a slice whose
#: outer table is wider tiers down with reason "ctx".  32 slabs keep the
#: two [NC*256, 128] int32 banks at 8 MiB.
_NC_CAP = 32

#: Default output chunk per lane per grid step (bytes, power of two).
_DEFAULT_CHUNK = 1024

# Packed per-lane register rows in the ``st`` scratch bank.
_S_R0 = 0        # rANS states R0..R3 in rows 0..3
_S_L0 = 4        # last-symbol (order-1 context) per state in rows 4..7
_S_P = 8         # renorm byte cursor
_S_OK = 9
_ST_ROWS = 16

# Per-lane launch meta rows.
_M_NOUT = 0
_M_4Q4 = 1       # 4*q4v: the wave index where state select locks to 3
_M_CLEN = 2
_M_R = 3         # initial states in rows 3..6
_META_ROWS = 8


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def stream_geometry(
    max_clen: int,
    max_osize: int,
    n_ctx: int,
    chunk_bytes: int = _DEFAULT_CHUNK,
) -> dict:
    """Static launch geometry (pure host math — also the tier-selection
    surface: ``vmem_bytes`` against the budget decides size-based
    tier-downs without touching a device)."""
    chunk_bytes = max(256, chunk_bytes)
    if chunk_bytes & (chunk_bytes - 1):
        raise ValueError("chunk_bytes must be a power of two")
    oc_words = chunk_bytes // 4
    r_words = _round_up(max(-(-max_clen // 4) + 2, 32), 512)
    ncb = 1
    while ncb < max(n_ctx, 1):
        ncb *= 2
    n_chunks = max(1, -(-max(max_osize, 1) // chunk_bytes))
    vmem = (
        r_words
        + 2 * ncb * 256
        + 256
        + oc_words
        + _ST_ROWS
        + _META_ROWS
        + 768
    ) * LANES * 4
    return {
        "r_words": r_words,
        "ncb": ncb,
        "oc_words": oc_words,
        "n_chunks": n_chunks,
        "vmem_bytes": vmem,
    }


def accepts(
    clen: int,
    osize: int,
    n_ctx: int,
    chunk_bytes: int = _DEFAULT_CHUNK,
) -> Tuple[bool, str]:
    """Would the lanes tier take a slice of this shape?  Returns
    ``(True, "")`` or ``(False, reason)`` with reason in
    ``{"size", "vmem", "ctx"}`` — the tier-down taxonomy
    ``cram_codecs.decompress_batch`` counts."""
    if osize > _MAX_OSIZE:
        return False, "size"
    if n_ctx > _NC_CAP:
        return False, "ctx"
    geo = stream_geometry(clen, osize, n_ctx, chunk_bytes)
    if geo["vmem_bytes"] > _VMEM_BUDGET_BYTES:
        return False, "vmem"
    return True, ""


def _kernel_factory(R_WORDS: int, NCB: int, OC_WORDS: int):
    """R_WORDS renorm-stream words/lane resident; NCB dense context
    slabs/lane; OC_WORDS output words/lane streamed per grid step."""

    def kernel(
        streams_ref, meta_ref, fbank_ref, cbank_ref, cmap_ref,
        out_ref, ok_ref, st_ref,
    ):
        k = pl.program_id(0)
        n_out = meta_ref[_M_NOUT:_M_NOUT + 1, :]
        fourq4 = meta_ref[_M_4Q4:_M_4Q4 + 1, :]
        clen = meta_ref[_M_CLEN:_M_CLEN + 1, :]
        rows_st = lax.broadcasted_iota(jnp.int32, (_ST_ROWS, LANES), 0)

        @pl.when(k == 0)
        def _init():
            st0 = jnp.zeros((_ST_ROWS, LANES), jnp.int32)
            for j in range(4):
                st0 = jnp.where(
                    rows_st == _S_R0 + j, meta_ref[_M_R + j:_M_R + j + 1, :],
                    st0,
                )
            st0 = jnp.where(rows_st == _S_OK, 1, st0)
            st_ref[:, :] = st0

        streams = streams_ref[:, :]
        fbank = fbank_ref[:, :]
        cbank = cbank_ref[:, :]
        cmap = cmap_ref[:, :]
        rows_bank = lax.broadcasted_iota(jnp.int32, (NCB * 256, LANES), 0)
        bank_ctx = lax.shift_right_logical(rows_bank, 8)
        bank_sym = rows_bank & 255
        rows_cmap = lax.broadcasted_iota(jnp.int32, (256, LANES), 0)
        rows_out = lax.broadcasted_iota(jnp.int32, (OC_WORDS, LANES), 0)
        rows_str = lax.broadcasted_iota(jnp.int32, (R_WORDS, LANES), 0)

        def strow(st, r):
            return jnp.sum(
                jnp.where(rows_st == r, st, 0), axis=0, keepdims=True
            )

        def body(w, carry):
            tile, st = carry
            word = jnp.zeros((1, LANES), jnp.int32)
            p = strow(st, _S_P)
            okv = strow(st, _S_OK)
            t0 = (k * OC_WORDS + w) * 4
            for jj in range(4):
                t = t0 + jj
                # State select: j = t&3 (== jj) in the quarters, 3 in
                # the order-1 remainder tail.
                j = jnp.where(t < fourq4, jj, 3)
                live = (t < n_out) & (okv == 1)
                Rj = jnp.sum(
                    jnp.where(rows_st == j, st, 0), axis=0, keepdims=True
                )
                lastj = jnp.sum(
                    jnp.where(rows_st == _S_L0 + j, st, 0),
                    axis=0, keepdims=True,
                )
                ci = jnp.sum(
                    jnp.where(rows_cmap == lastj, cmap, 0),
                    axis=0, keepdims=True,
                )
                # Context absent from the slice's table: invariant
                # breach — flag and let the host tiers resolve it.
                okv = jnp.where(live & (ci < 0), 0, okv)
                ci = jnp.maximum(ci, 0)
                m = Rj & (_TOTFREQ - 1)
                in_slab = bank_ctx == ci
                # searchsorted-as-reduction: the owning symbol is
                # |{s : C[s] <= m}| - 1 within the active slab.
                s = jnp.sum(
                    jnp.where(in_slab & (cbank <= m), 1, 0),
                    axis=0, keepdims=True,
                ) - 1
                s = jnp.maximum(s, 0)
                pick = in_slab & (bank_sym == s)
                Fv = jnp.sum(jnp.where(pick, fbank, 0), axis=0, keepdims=True)
                Cv = jnp.sum(jnp.where(pick, cbank, 0), axis=0, keepdims=True)
                Rn = Fv * lax.shift_right_logical(Rj, _TF_SHIFT) + m - Cv
                # Renormalize: at most two byte reads bring any valid
                # state back above L (encoder keeps post-renorm states
                # >= 2^11); still below after two means corrupt.
                for _ in range(2):
                    need = live & (Rn < _RANS_L)
                    wv = jnp.sum(
                        jnp.where(
                            rows_str == lax.shift_right_logical(p, 2),
                            streams, 0,
                        ),
                        axis=0, keepdims=True,
                    )
                    byte = lax.shift_right_logical(wv, 8 * (p & 3)) & 255
                    okv = jnp.where(need & (p >= clen), 0, okv)
                    Rn = jnp.where(need, (Rn << 8) | byte, Rn)
                    p = p + need.astype(jnp.int32)
                okv = jnp.where(live & (Rn < _RANS_L), 0, okv)
                st = jnp.where((rows_st == j) & live, Rn, st)
                st = jnp.where((rows_st == _S_L0 + j) & live, s, st)
                word = word | jnp.where(live, s << (8 * jj), 0)
            st = jnp.where(rows_st == _S_P, p, st)
            st = jnp.where(rows_st == _S_OK, okv, st)
            tile = jnp.where(rows_out == w, word, tile)
            return tile, st

        tile, st = lax.fori_loop(
            0, OC_WORDS, body,
            (jnp.zeros((OC_WORDS, LANES), jnp.int32), st_ref[:, :]),
        )
        st_ref[:, :] = st
        out_ref[:, :] = tile
        ok_ref[:, :] = jnp.sum(
            jnp.where(rows_st == _S_OK, st, 0), axis=0, keepdims=True
        )

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("r_words", "ncb", "oc_words", "n_chunks", "interpret"),
)
def _rans_lanes_kernel(
    streams, meta, fbank, cbank, cmap,
    r_words: int, ncb: int, oc_words: int, n_chunks: int, interpret: bool,
):
    kernel = _kernel_factory(r_words, ncb, oc_words)
    return pl.pallas_call(
        kernel,
        grid=(n_chunks,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 5,
        out_specs=(
            pl.BlockSpec(
                (oc_words, LANES), lambda k: (k, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, LANES), lambda k: (0, 0), memory_space=pltpu.VMEM
            ),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n_chunks * oc_words, LANES), jnp.int32),
            jax.ShapeDtypeStruct((1, LANES), jnp.int32),
        ),
        scratch_shapes=[pltpu.VMEM((_ST_ROWS, LANES), jnp.int32)],
        interpret=interpret,
    )(streams, meta, fbank, cbank, cmap)


def _group_geometry(group, chunk_bytes):
    max_clen = max(len(p.payload) for _, p in group)
    max_osize = max(p.n_out for _, p in group)
    n_ctx = max(len(p.tables) for _, p in group)
    return stream_geometry(max_clen, max_osize, n_ctx, chunk_bytes)


def rans_lanes(
    blocks: Sequence[bytes],
    chunk_bytes: int = _DEFAULT_CHUNK,
    interpret=None,
) -> Tuple[List[Optional[bytes]], "_cc.RansTierStats"]:
    """Batched lockstep decode of rANS 4x8 streams, up to 128 per kernel
    launch, output streamed chunk-by-chunk to HBM.

    Returns ``(outs, stats)``: per-slice decoded bytes with ``None`` for
    every slice that tiered down (bad format, size/VMEM/context caps, or
    an in-kernel ``ok=0``) — the caller rescues those through the NumPy
    host tier and the Python oracle — plus the
    :class:`~hadoop_bam_tpu.spec.cram_codecs.RansTierStats` taxonomy of
    what went where.  Tier-down is per slice, never per launch."""
    stats = _cc.RansTierStats()
    B = len(blocks)
    outs: List[Optional[bytes]] = [None] * B
    accepted = []
    for i, data in enumerate(blocks):
        try:
            plan = _cc.parse_rans_plan(data)
        except Exception:
            stats.tierdown_format += 1
            continue
        if plan.n_out == 0:
            outs[i] = b""
            stats.lanes += 1
            continue
        ok, reason = accepts(
            len(plan.payload), plan.n_out, len(plan.tables), chunk_bytes
        )
        if not ok:
            setattr(
                stats, f"tierdown_{reason}",
                getattr(stats, f"tierdown_{reason}") + 1,
            )
            continue
        accepted.append((i, plan))
    if not accepted:
        return outs, stats
    interpret = pallas_interpret(interpret)
    # Pack launch groups greedily: lane-capped at 128 and VMEM-capped on
    # the running group maxima (slices pass the per-slice gate alone, but
    # a wide-context slice and a long slice can only share a launch if
    # their combined banks still fit).
    groups = []
    cur: list = []
    for item in accepted:
        cand = cur + [item]
        if len(cand) > LANES or (
            cur
            and _group_geometry(cand, chunk_bytes)["vmem_bytes"]
            > _VMEM_BUDGET_BYTES
        ):
            groups.append(cur)
            cur = [item]
        else:
            cur = cand
    if cur:
        groups.append(cur)
    for group in groups:
        _launch_group(group, outs, stats, chunk_bytes, bool(interpret))
    return outs, stats


def _launch_group(group, outs, stats, chunk_bytes, interpret):
    geo = _group_geometry(group, chunk_bytes)
    r_words = geo["r_words"]
    ncb = geo["ncb"]
    oc_words = geo["oc_words"]
    n_chunks = geo["n_chunks"]
    n = len(group)
    grp = np.zeros((r_words * 4, LANES), dtype=np.uint8)
    meta = np.zeros((_META_ROWS, LANES), dtype=np.int32)
    fbank = np.zeros((ncb * 256, LANES), dtype=np.int32)
    cbank = np.zeros((ncb * 256, LANES), dtype=np.int32)
    cmap = np.full((256, LANES), -1, dtype=np.int32)
    for j, (_, plan) in enumerate(group):
        pay = np.frombuffer(plan.payload, dtype=np.uint8)
        grp[: len(pay), j] = pay
        meta[_M_NOUT, j] = plan.n_out
        meta[_M_4Q4, j] = 4 * plan.q4v
        meta[_M_CLEN, j] = len(pay)
        meta[_M_R:_M_R + 4, j] = (
            np.array(plan.states, dtype=np.uint32).view(np.int32)
        )
        if plan.order == 0:
            cmap[:, j] = 0
        for ci, (ctx, (F, C, _lk)) in enumerate(sorted(plan.tables.items())):
            if plan.order == 1:
                cmap[ctx, j] = ci
            fbank[ci * 256:(ci + 1) * 256, j] = F
            cbank[ci * 256:(ci + 1) * 256, j] = C[:256]
    words = (
        grp.reshape(r_words, 4, LANES).astype(np.uint32)
        * (np.uint32(1) << (8 * np.arange(4, dtype=np.uint32)))[
            None, :, None
        ]
    ).sum(axis=1).astype(np.uint32).view(np.int32)
    owords, okk = _rans_lanes_kernel(
        jnp.asarray(words), jnp.asarray(meta), jnp.asarray(fbank),
        jnp.asarray(cbank), jnp.asarray(cmap),
        r_words, ncb, oc_words, n_chunks, interpret,
    )
    by = np.asarray(owords).view(np.uint32)
    out_cap = n_chunks * oc_words * 4
    bytes_mat = np.zeros((out_cap, LANES), dtype=np.uint8)
    for k in range(4):
        bytes_mat[k::4] = ((by >> np.uint32(8 * k)) & 0xFF).astype(np.uint8)
    okk = np.asarray(okk)[0].astype(bool)
    for j, (i, plan) in enumerate(group):
        if okk[j]:
            outs[i] = _cc.rans_deinterleave(
                bytes_mat[: plan.n_out, j], plan.order, plan.n_out
            )
            stats.lanes += 1
        else:
            stats.tierdown_ok0 += 1
