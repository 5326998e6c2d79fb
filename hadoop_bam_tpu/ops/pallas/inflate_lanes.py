"""Lockstep-lane Pallas inflate for *general* DEFLATE members, HBM-streaming.

The production promotion of the walk engine of
ops/pallas/inflate_probe.py: up to 128 BGZF members ride the 128
vector lanes of one kernel, each walking its own DEFLATE bit stream
serially through any per-member mix of stored/fixed/dynamic blocks.

Architecture (all stages share the probe's register/VMEM-resident style —
per-lane row selects are dense iota-compare column reductions, never
gathers):

- streams live TRANSPOSED in VMEM ([words, 128]: member j's words go down
  lane j); "read 32 bits at my cursor" is two one-hot row selects;
- per-member canonical Huffman tables are built ON CHIP per block — the
  length histogram, first-code and symbol-offset columns are static
  15-step loops over [1,128] rows, and the canonical symbol ranking is a
  288-step lockstep scan with one-hot scatters (semantics pinned to
  ops/flate.py's ``_canonical_decoder``/``_kraft_valid``, the spec);
- decode is the 15-compare canonical range test of the probe, against the
  per-lane table columns — pure elementwise VPU work;
- emit is a byte-per-wave state machine: every wave each live lane either
  emits one literal, copies one LZ77 byte back from its own output
  window, streams one stored-block byte, decodes a length/distance pair,
  or retires its block on EOB — so lanes with different block types and
  token mixes stay in lockstep.

**Streaming geometry** (the lift of the old whole-member-VMEM cap): the
kernel grids over fixed-size OUTPUT chunks (``chunk_bytes`` per lane per
grid step).  Only one chunk tile is live in VMEM at a time; finished
tiles stream out to the HBM-backed output as the grid advances.  Per-lane
state carries across grid steps in VMEM scratch:

- the bit cursor, output cursor, ok/done flags, copy/stored progress and
  the far-copy ledger live in a packed register file (``st``);
- the current block's canonical litlen/dist tables persist in a packed
  table bank (``tabs``) so a block can span any number of chunks;
- LZ77 copies resolve from a **ring window** of the lane's last
  ``ring_bytes`` output bytes (sized to cover DEFLATE's full 32 KiB
  distance domain by default, so no legal copy ever leaves the window);
  copies farther than ``far_dist`` — and any later copy whose source
  could overlap a deferred destination — are recorded in a small per-lane
  side list and replayed by a host-assisted pass after download (never
  taken with the default window; exercised by the windowed test configs);
- block headers are parsed (and tables rebuilt) *between* emit phases,
  inside per-step rounds, gated by ``lax.cond`` so steps that resume
  mid-block pay no table-build cost;
- one epilogue grid step runs past the last output chunk so a member
  whose final EOB lands exactly on a chunk boundary still retires.

A full 64 KiB BGZF member (the cap real writers emit at) now decodes on
the lanes tier: VMEM holds the compressed words, the 32 KiB ring and one
chunk tile — about 13.5 MiB at the worst-case geometry — instead of the
old input + whole output residency that tiered everything past ~10 KiB
down to the XLA/host decoders.  Members whose *compressed* stream alone
exceeds the VMEM budget (impossible for BGZF, relevant only to future
CRAM containers) still come back ``ok=False`` and tier down, as do
corrupt members, via the per-member ``[n_out, ok]`` meta.

Oracle: zlib via tests/test_inflate_lanes.py and the streaming corpus in
tests/test_stream_codecs.py; tests run the kernel in interpret mode on
CPU and compare byte-for-byte.
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils.backend import pallas_interpret
from ...utils.tracing import span

from ..flate import CLC_ORDER, DIST_BASE, DIST_EXTRA, LEN_BASE, LEN_EXTRA

LANES = 128

#: Code-length section is ≤ 286+30 = 316 codes; RLE tokens never exceed it.
_MAX_CODES = 320
_MAX_HDR_TOKENS = 318

#: Scoped-VMEM limit the kernel is compiled with (the compiler's own
#: default is 16 MiB; a v5e core has 128 MiB).  The full-member
#: geometry counts ~21.7 MiB (tests/test_chip_compile.py).
_VMEM_LIMIT_BYTES = 32 << 20

#: VMEM budget for one launch, against :func:`stream_geometry`'s
#: ``vmem_bytes`` (which tracks the compiler's count); the rest of the
#: limit is headroom.  Members whose geometry exceeds it come back
#: ok=False and tier down.
_VMEM_BUDGET_BYTES = _VMEM_LIMIT_BYTES - (4 << 20)

#: Output-size sanity cap (BGZF members are ≤ 64 KiB; the margin is for
#: future CRAM containers).  Past it the wrapper declines without
#: launching.
_MAX_ISIZE = 1 << 20

#: Default output chunk per lane per grid step (must be a power of two).
_DEFAULT_CHUNK = 4096

# Packed per-lane register rows in the ``st`` scratch bank.
_R_CUR = 0        # bit cursor
_R_NOUT = 1       # output byte cursor
_R_OK = 2
_R_DONE = 3
_R_INBLK = 4      # mid-block (tables/stored state valid)
_R_STORED = 5     # current block is stored
_R_BFINAL = 6     # current block carries BFINAL
_R_CREM = 7       # LZ77 copy bytes remaining
_R_CDIST = 8      # LZ77 copy distance
_R_SREM = 9       # stored-block bytes remaining
_R_FARC = 10      # far-copy events recorded
_R_HOLE = 11      # lowest deferred-destination start
_R_BLK = 12       # blocks started
_ST_ROWS = 16

# Packed table bank rows: litlen syms, dist syms, then the 16-row
# first/count/symoff columns for each alphabet.
_T_LLSYM = 0          # [0, 288)
_T_DLSYM = 288        # [288, 320)
_T_LLFIRST = 320      # [320, 336)
_T_LLCOUNT = 336
_T_LLSYMOFF = 352
_T_DLFIRST = 368
_T_DLCOUNT = 384
_T_DLSYMOFF = 400
_TAB_ROWS = 416


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _sel_const(idx: jnp.ndarray, table: np.ndarray) -> jnp.ndarray:
    """Per-lane select from a small static table: out[lane]=table[idx[lane]]
    as a static compare loop (no gather)."""
    out = jnp.zeros_like(idx)
    for k in range(len(table)):
        out = jnp.where(idx == k, int(table[k]), out)
    return out


def _rev_bits(w: jnp.ndarray, n: int) -> jnp.ndarray:
    """Reverse the low ``n`` bits of uint32 ``w`` (stream bit 0 → MSB)."""
    r = jnp.zeros_like(w)
    for k in range(n):
        r = r | (((w >> k) & 1) << (n - 1 - k))
    return r.astype(jnp.int32)


def _build_canon(lens: jnp.ndarray, S: int, maxl: int):
    """Per-lane canonical tables from code lengths (``_canonical_decoder``
    semantics, lockstep form).

    ``lens``: int32 [S, 128].  Returns ``(first, count, symoff)`` as python
    lists of [1,128] columns indexed by code length, plus ``sym_sorted``
    [S,128]: a code of length L and MSB-first value c decodes to
    ``sym_sorted[symoff[L] + c - first[L]]``.
    """
    count = [jnp.zeros((1, LANES), jnp.int32)]
    for L in range(1, maxl + 1):
        count.append(
            jnp.sum((lens == L).astype(jnp.int32), axis=0, keepdims=True)
        )
    first = [jnp.zeros((1, LANES), jnp.int32)]
    code = jnp.zeros((1, LANES), jnp.int32)
    for L in range(1, maxl + 1):
        code = (code + count[L - 1]) << 1
        first.append(code)
    symoff = []
    acc = jnp.zeros((1, LANES), jnp.int32)
    for L in range(0, maxl + 1):
        symoff.append(acc)
        acc = acc + count[L]
    # Canonical symbol ranking: lockstep scan over the symbol axis; each
    # step places one symbol per lane via a one-hot row scatter.
    rows_S = lax.broadcasted_iota(jnp.int32, (S, LANES), 0)
    rows_L = lax.broadcasted_iota(jnp.int32, (maxl + 1, LANES), 0)

    def sbody(s, st):
        sym_sorted, taken = st
        len_s = jnp.sum(
            jnp.where(rows_S == s, lens, 0), axis=0, keepdims=True
        )
        rank = jnp.zeros((1, LANES), jnp.int32)
        for L in range(1, maxl + 1):
            rank = jnp.where(
                len_s == L, symoff[L] + taken[L : L + 1, :], rank
            )
        use = len_s > 0
        sym_sorted = jnp.where((rows_S == rank) & use, s, sym_sorted)
        taken = jnp.where((rows_L == len_s) & use, taken + 1, taken)
        return sym_sorted, taken

    sym_sorted, _ = lax.fori_loop(
        0,
        S,
        sbody,
        (
            jnp.zeros((S, LANES), jnp.int32),
            jnp.zeros((maxl + 1, LANES), jnp.int32),
        ),
    )
    return first, count, symoff, sym_sorted


def _kraft_ok(count, maxl: int, allow_single: bool) -> jnp.ndarray:
    """Per-lane Kraft validity of a length histogram (``_kraft_valid``
    semantics: reject over-subscribed and incomplete sets, except zlib's
    lone length-1 code grace when ``allow_single``)."""
    kraft = jnp.zeros((1, LANES), jnp.int32)
    ncodes = jnp.zeros((1, LANES), jnp.int32)
    for L in range(1, maxl + 1):
        kraft = kraft + (count[L] << (maxl - L))
        ncodes = ncodes + count[L]
    ok = (ncodes == 0) | (kraft == (1 << maxl))
    if allow_single:
        ok = ok | ((ncodes == 1) & (count[1] == 1))
    return ok


def _canon_decode(rev, first, count, symoff, sym_sorted, maxl, rows_S):
    """15-compare canonical decode of MSB-first-reversed windows against
    per-lane tables (``first``/``count``/``symoff`` index by code length:
    either python lists of [1,128] columns or stacked [16,128] banks).
    Returns (sym, L, matched); speculative garbage positions may be
    unmatched."""

    def row(t, L):
        return t[L] if isinstance(t, list) else t[L : L + 1, :]

    S = sym_sorted.shape[0]
    Lsel = jnp.full((1, LANES), 99, jnp.int32)
    f_s = jnp.zeros((1, LANES), jnp.int32)
    o_s = jnp.zeros((1, LANES), jnp.int32)
    for L in range(maxl, 0, -1):  # downward: smallest L wins last
        cand = rev >> (maxl - L)
        match = (cand >= row(first, L)) & (
            cand < row(first, L) + row(count, L)
        )
        Lsel = jnp.where(match, L, Lsel)
        f_s = jnp.where(match, row(first, L), f_s)
        o_s = jnp.where(match, row(symoff, L), o_s)
    matched = Lsel < 99
    Ls = jnp.where(matched, Lsel, 1)
    cand = rev >> (maxl - Ls)
    idx = jnp.clip(o_s + cand - f_s, 0, S - 1)
    sym = jnp.sum(
        jnp.where(rows_S == idx, sym_sorted, 0), axis=0, keepdims=True
    )
    return sym, Ls, matched


def _flags(*masks):
    """int32 0/1 lane masks → bool lane flags."""
    return tuple(m != 0 for m in masks)


def _masks(*flags):
    """bool lane flags → int32 0/1 lane masks (the loop-carried form)."""
    return tuple(f.astype(jnp.int32) for f in flags)


def _stack16(cols) -> jnp.ndarray:
    """[1,128] column list (len ≤ 16, indexed by code length) → [16,128]."""
    pad = [jnp.zeros((1, LANES), jnp.int32)] * (16 - len(cols))
    return jnp.concatenate(list(cols) + pad, axis=0)


def stream_geometry(
    max_clen: int,
    max_isize: int,
    chunk_bytes: int = _DEFAULT_CHUNK,
    far_dist: int = 1 << 15,
    max_far: int = 64,
    max_blocks: int = 12,
) -> dict:
    """Static launch geometry for the streaming decoder (pure host math —
    also the tier-selection surface: ``vmem_bytes`` against the budget
    decides size-based tier-downs without touching a device)."""
    chunk_bytes = max(256, chunk_bytes)
    if chunk_bytes & (chunk_bytes - 1):
        raise ValueError("chunk_bytes must be a power of two")
    oc_rows = chunk_bytes // 4
    # The resolve ring only has to cover distances that can actually
    # occur: DEFLATE caps them at 32768 and a member can never reference
    # before its own start, so small members get a small (cheap) ring.
    win = 1
    while win < min(max(max_isize, 1), 1 << 15):
        win *= 2
    ring_bytes = chunk_bytes
    while ring_bytes < min(far_dist, 1 << 15, win):
        ring_bytes *= 2
    # The static in-kernel threshold tracks the ring, not the member: any
    # distance the ring can hold resolves on chip, and tying the launch
    # signature to (chunk, ring) alone keeps jit recompiles rare.
    eff_far = min(far_dist, ring_bytes)
    r_words = _round_up(max(-(-max_clen // 4) + 2, 32), 512)
    n_chunks = -(-max(max_isize, 1) // chunk_bytes) + 1  # +1 epilogue
    t_step = (max_blocks + 2) * (chunk_bytes + chunk_bytes // 2 + 64)
    # The stream counts twice: resident, plus the one-hot select's
    # stream-sized temporary in every cursor read.
    vmem = (
        2 * r_words
        + ring_bytes // 4
        + oc_rows
        + 2 * _TAB_ROWS
        + 2 * _MAX_CODES
        + 4 * max_far
        + _ST_ROWS
        + 768
    ) * LANES * 4
    return {
        "r_words": r_words,
        "oc_rows": oc_rows,
        "ring_rows": ring_bytes // 4,
        "n_chunks": n_chunks,
        "t_step": t_step,
        "far_dist": eff_far,
        "vmem_bytes": vmem,
    }


def accepts(
    max_clen: int, max_isize: int, chunk_bytes: int = _DEFAULT_CHUNK
) -> Tuple[bool, str]:
    """Would the streaming lanes tier take a member of this shape?

    Pure host logic (no jax import needed at decision time beyond module
    load): returns ``(True, "")`` or ``(False, reason)`` with reason in
    ``{"size", "vmem"}`` — the tier-down taxonomy the flate wrappers
    count.  A full 64 KiB BGZF member is accepted."""
    if max_isize > _MAX_ISIZE:
        return False, "size"
    geo = stream_geometry(max_clen, max_isize, chunk_bytes)
    if geo["vmem_bytes"] > _VMEM_BUDGET_BYTES:
        return False, "vmem"
    return True, ""


def _kernel_factory(
    R: int,
    OC_ROWS: int,
    RING_ROWS: int,
    T_STEP: int,
    MAX_BLOCKS: int,
    MAX_FAR: int,
    FAR_DIST: int,
):
    """R stream words/lane resident; OC_ROWS output words/lane streamed per
    grid step; RING_ROWS LZ77 resolve window; T_STEP wave budget/step."""
    OC_BYTES = OC_ROWS * 4
    MAX_ROUNDS = MAX_BLOCKS + 2

    def kernel(
        streams_ref,
        nbits_ref,
        isize_ref,
        out_ref,
        nout_ref,
        ok_ref,
        farc_ref,
        fara_ref,
        farb_ref,
        ring_ref,
        st_ref,
        tabs_ref,
        fa_ref,
        fb_ref,
    ):
        k = pl.program_id(0)
        rows_R = lax.broadcasted_iota(jnp.int32, (R, LANES), 0)
        rows_RING = lax.broadcasted_iota(jnp.int32, (RING_ROWS, LANES), 0)
        rows_ll = lax.broadcasted_iota(jnp.int32, (288, LANES), 0)
        rows_dl = lax.broadcasted_iota(jnp.int32, (32, LANES), 0)
        rows_cl = lax.broadcasted_iota(jnp.int32, (19, LANES), 0)
        rows_hc = lax.broadcasted_iota(jnp.int32, (_MAX_CODES, LANES), 0)
        rows_F = lax.broadcasted_iota(jnp.int32, (MAX_FAR, LANES), 0)
        rows_st = lax.broadcasted_iota(jnp.int32, (_ST_ROWS, LANES), 0)
        nbits = nbits_ref[:, :]
        isize = isize_ref[:, :]

        @pl.when(k == 0)
        def _init():
            init = jnp.zeros((_ST_ROWS, LANES), jnp.int32)
            init = jnp.where(
                (rows_st == _R_OK) & jnp.broadcast_to(nbits > 0, init.shape),
                1,
                init,
            )
            init = jnp.where(
                (rows_st == _R_DONE)
                & jnp.broadcast_to(nbits == 0, init.shape),
                1,
                init,
            )
            init = jnp.where(
                rows_st == _R_HOLE, jnp.int32(0x7FFFFFFF), init
            )
            st_ref[:, :] = init
            tabs_ref[:, :] = jnp.zeros((_TAB_ROWS, LANES), jnp.int32)
            fa_ref[:, :] = jnp.zeros((MAX_FAR, LANES), jnp.int32)
            fb_ref[:, :] = jnp.zeros((MAX_FAR, LANES), jnp.int32)
            ring_ref[:, :] = jnp.zeros((RING_ROWS, LANES), jnp.int32)

        chunk_end = (k + 1) * OC_BYTES

        def word_at(widx):
            onehot = rows_R == widx
            return jnp.sum(
                jnp.where(onehot, streams_ref[:, :], 0),
                axis=0,
                keepdims=True,
            ).astype(jnp.uint32)

        def window(cur):
            """32 stream bits at per-lane bit cursor ``cur`` [1,128]."""
            widx = cur >> 5
            w0 = word_at(widx)
            w1 = word_at(widx + 1)
            sh = (cur & 31).astype(jnp.uint32)
            return jnp.where(sh == 0, w0, (w0 >> sh) | (w1 << (32 - sh)))

        def ring_byte_at(rv, pos):
            """Byte at global output position ``pos`` from the ring
            snapshot ``rv`` (valid within the last RING_ROWS*4 bytes)."""
            wrow = (pos >> 2) & (RING_ROWS - 1)
            word = jnp.sum(
                jnp.where(rows_RING == wrow, rv, 0),
                axis=0,
                keepdims=True,
            ).astype(jnp.uint32)
            return (word >> (8 * (pos & 3)).astype(jnp.uint32)) & 0xFF

        # Fixed-Huffman length vectors (RFC 1951 §3.2.6), built from iota
        # in-kernel (Pallas kernels cannot capture array constants).
        fixed_ll = jnp.where(
            rows_ll < 144,
            8,
            jnp.where(rows_ll < 256, 9, jnp.where(rows_ll < 280, 7, 8)),
        ).astype(jnp.int32)
        fixed_dl = jnp.full((32, LANES), 5, jnp.int32)

        # ---- restore the carried member state ---------------------------
        st = st_ref[:, :]

        def reg(r):
            return st[r : r + 1, :]

        cur0 = reg(_R_CUR)
        n_out0 = reg(_R_NOUT)
        # Lane flags cross loop/cond boundaries as int32 0/1 masks:
        # Mosaic cannot carry (or select between) i1 vectors there.
        ok0 = reg(_R_OK)
        done0 = reg(_R_DONE)
        inblk0 = reg(_R_INBLK)
        stored0 = reg(_R_STORED)
        bfin0 = reg(_R_BFINAL)
        crem0 = reg(_R_CREM)
        cdist0 = reg(_R_CDIST)
        srem0 = reg(_R_SREM)
        farc0 = reg(_R_FARC)
        hole0 = reg(_R_HOLE)
        blk0 = reg(_R_BLK)
        tabs0 = tabs_ref[:, :]
        fara0 = fa_ref[:, :]
        farb0 = fb_ref[:, :]

        # ---- header parse + table build (one new block per round) -------
        def parse_fn(c):
            (cur, n_out, okv, done, inblk, stored, bfin, crem, cdist,
             srem, farc, hole, blk, tabs, fara, farb) = c
            okv, done, inblk, stored, bfin = _flags(
                okv, done, inblk, stored, bfin
            )
            need = okv & ~done & ~inblk & (n_out < chunk_end)
            hdr = window(cur)
            bfinal = (hdr & 1) == 1
            btype = ((hdr >> 1) & 3).astype(jnp.int32)
            okv = okv & (~need | (btype != 3))
            blk = blk + need.astype(jnp.int32)
            okv = okv & (~need | (blk <= MAX_BLOCKS))
            is_stored = need & (btype == 0)
            is_dyn = need & (btype == 2)

            # stored block setup (byte-aligned LEN/NLEN)
            st_bit = (cur + 3 + 7) & ~7
            ln_w = window(st_bit)
            s_len = (ln_w & 0xFFFF).astype(jnp.int32)
            s_nlen = ((ln_w >> 16) & 0xFFFF).astype(jnp.int32)
            okv = okv & (
                ~is_stored
                | (
                    (s_len == (s_nlen ^ 0xFFFF))
                    & (st_bit + 32 + 8 * s_len <= nbits)
                )
            )

            # dynamic header parse (btype=10)
            at = cur + 3
            hlit = (window(at) & 31).astype(jnp.int32) + 257
            hdist = (window(at + 5) & 31).astype(jnp.int32) + 1
            hclen = (window(at + 10) & 15).astype(jnp.int32) + 4
            okv = okv & (~is_dyn | ((hlit <= 286) & (hdist <= 30)))
            cl_lens = jnp.zeros((19, LANES), jnp.int32)
            for i in range(19):
                bits = (window(at + 14 + 3 * i) & 7).astype(jnp.int32)
                bits = jnp.where(i < hclen, bits, 0)
                cl_lens = jnp.where(
                    rows_cl == int(CLC_ORDER[i]), bits, cl_lens
                )
            clc = _build_canon(cl_lens, 19, 7)
            okv = okv & (
                ~is_dyn | _kraft_ok(clc[1], 7, allow_single=False)
            )
            total_codes = hlit + hdist

            # Code-length RLE: one CLC token per wave, lockstep across
            # lanes; repeats land as masked row-range writes.
            def hcond(s):
                pos, cnt, prev, okh, lens_all, it = s
                act = is_dyn & (okh != 0) & (cnt < total_codes)
                return (it < _MAX_HDR_TOKENS) & jnp.any(act)

            def hbody(s):
                pos, cnt, prev, okh, lens_all, it = s
                okh = okh != 0
                w = window(pos)
                r7 = _rev_bits(w, 7)
                csym, cL, cm = _canon_decode(
                    r7, clc[0], clc[1], clc[2], clc[3], 7, rows_cl
                )
                ext = (w >> cL.astype(jnp.uint32)).astype(jnp.int32)
                rep = jnp.where(
                    csym < 16,
                    1,
                    jnp.where(
                        csym == 16,
                        3 + (ext & 3),
                        jnp.where(
                            csym == 17, 3 + (ext & 7), 11 + (ext & 127)
                        ),
                    ),
                )
                val = jnp.where(
                    csym < 16, csym, jnp.where(csym == 16, prev, 0)
                )
                nb = cL + jnp.where(
                    csym < 16,
                    0,
                    jnp.where(
                        csym == 16, 2, jnp.where(csym == 17, 3, 7)
                    ),
                )
                act = is_dyn & okh & (cnt < total_codes)
                okh = okh & (~act | cm)
                wr = act & okh
                lens_all = jnp.where(
                    (rows_hc >= cnt) & (rows_hc < cnt + rep) & wr,
                    val,
                    lens_all,
                )
                pos = pos + jnp.where(wr, nb, 0)
                cnt = cnt + jnp.where(wr, rep, 0)
                prev = jnp.where(wr, val, prev)
                okh = okh.astype(jnp.int32)
                return pos, cnt, prev, okh, lens_all, it + 1

            hpos, hcnt, _, hok, lens_all, _ = lax.while_loop(
                hcond,
                hbody,
                (
                    at + 14 + 3 * hclen,
                    jnp.zeros((1, LANES), jnp.int32),
                    jnp.zeros((1, LANES), jnp.int32),
                    jnp.ones((1, LANES), jnp.int32),
                    jnp.zeros((_MAX_CODES, LANES), jnp.int32),
                    jnp.int32(0),
                ),
            )
            okv = okv & (
                ~is_dyn
                | ((hok != 0) & (hcnt == total_codes) & (hpos <= nbits))
            )

            dyn_ll = jnp.where(rows_ll < hlit, lens_all[:288, :], 0)
            dl_cols = []
            for d in range(32):
                col = jnp.sum(
                    jnp.where(rows_hc == hlit + d, lens_all, 0),
                    axis=0,
                    keepdims=True,
                )
                dl_cols.append(jnp.where(d < hdist, col, 0))
            dyn_dl = jnp.concatenate(dl_cols, axis=0)

            use_dyn = btype == 2
            ll_lens = jnp.where(use_dyn, dyn_ll, fixed_ll)
            dl_lens = jnp.where(use_dyn, dyn_dl, fixed_dl)
            ll = _build_canon(ll_lens, 288, 15)
            dl = _build_canon(dl_lens, 32, 15)
            okv = okv & (
                ~is_dyn
                | (
                    _kraft_ok(ll[1], 15, allow_single=True)
                    & _kraft_ok(dl[1], 15, allow_single=True)
                )
            )
            data_start = jnp.where(
                use_dyn, hpos, jnp.where(btype == 0, st_bit + 32, cur + 3)
            )

            # Merge new tables for lanes opening a Huffman block; stored
            # lanes keep their (unused) bank.
            merge = need & (btype != 0)
            tabs_new = jnp.concatenate(
                [
                    ll[3],
                    dl[3],
                    _stack16(ll[0]),
                    _stack16(ll[1]),
                    _stack16(ll[2]),
                    _stack16(dl[0]),
                    _stack16(dl[1]),
                    _stack16(dl[2]),
                ],
                axis=0,
            )
            tabs = jnp.where(merge, tabs_new, tabs)
            cur = jnp.where(need, data_start, cur)
            inblk = inblk | need
            # Boolean blends as logic, not selects: Mosaic cannot
            # truncate the i8 select result back to i1.
            stored = (need & is_stored) | (~need & stored)
            bfin = (need & bfinal) | (~need & bfin)
            srem = jnp.where(need, jnp.where(is_stored, s_len, 0), srem)
            okv, done, inblk, stored, bfin = _masks(
                okv, done, inblk, stored, bfin
            )
            return (cur, n_out, okv, done, inblk, stored, bfin, crem,
                    cdist, srem, farc, hole, blk, tabs, fara, farb)

        # ---- one emit phase: byte-per-wave until every lane stalls ------
        def emit_phase(c, wav):
            (cur, n_out, okv, done, inblk, stored, bfin, crem, cdist,
             srem, farc, hole, blk, tabs, fara, farb) = c
            ll_first = tabs[_T_LLFIRST:_T_LLCOUNT, :]
            ll_count = tabs[_T_LLCOUNT:_T_LLSYMOFF, :]
            ll_symoff = tabs[_T_LLSYMOFF:_T_DLFIRST, :]
            ll_syms = tabs[_T_LLSYM:_T_DLSYM, :]
            dl_first = tabs[_T_DLFIRST:_T_DLCOUNT, :]
            dl_count = tabs[_T_DLCOUNT:_T_DLSYMOFF, :]
            dl_symoff = tabs[_T_DLSYMOFF:_TAB_ROWS, :]
            dl_syms = tabs[_T_DLSYM:_T_LLFIRST, :]

            def econd(s):
                (it, cur, n_out, okv, done, inblk, stored, bfin, crem,
                 cdist, srem, farc, hole, fara, farb) = s
                okv, done, inblk = _flags(okv, done, inblk)
                act = okv & ~done & inblk & (n_out < chunk_end)
                return (it < T_STEP) & jnp.any(act)

            def ebody(s):
                (it, cur, n_out, okv, done, inblk, stored, bfin, crem,
                 cdist, srem, farc, hole, fara, farb) = s
                okv, done, inblk, stored, bfin = _flags(
                    okv, done, inblk, stored, bfin
                )
                active = okv & ~done & inblk & (n_out < chunk_end)
                in_copy = active & (crem > 0)
                in_stored = active & stored & (srem > 0)
                decode = active & ~stored & ~in_copy

                rv = ring_ref[:, :]
                # 1. LZ77 copy byte (reads before this wave's writes).
                cb = ring_byte_at(rv, n_out - cdist)
                # 2. stored byte (cursor is byte-aligned in stored blocks).
                sb = window(cur) & 0xFF
                # 3. token decode at the cursor.
                w = window(cur)
                sym, L, m = _canon_decode(
                    _rev_bits(w, 15), ll_first, ll_count, ll_symoff,
                    ll_syms, 15, rows_ll,
                )
                islit = decode & m & (sym < 256)
                iseob = decode & m & (sym == 256)
                islen = decode & m & (sym > 256) & (sym < 286)
                bad = decode & (~m | (sym >= 286))
                li = jnp.clip(sym - 257, 0, 28)
                le = _sel_const(li, LEN_EXTRA)
                lenval = _sel_const(li, LEN_BASE) + (
                    (w >> L.astype(jnp.uint32)).astype(jnp.int32)
                    & ((1 << le) - 1)
                )
                wd = window(cur + L + le)
                dsym, Ld, md = _canon_decode(
                    _rev_bits(wd, 15), dl_first, dl_count, dl_symoff,
                    dl_syms, 15, rows_dl,
                )
                bad = bad | (islen & (~md | (dsym >= 30)))
                dsym = jnp.clip(dsym, 0, 29)
                de = _sel_const(dsym, DIST_EXTRA)
                dist = _sel_const(dsym, DIST_BASE) + (
                    (wd >> Ld.astype(jnp.uint32)).astype(jnp.int32)
                    & ((1 << de) - 1)
                )
                adv = jnp.where(islit | iseob, L, L + le + Ld + de)
                bad = bad | (decode & (cur + adv > nbits))
                bad = bad | (islen & (dist > n_out))
                islit = islit & ~bad
                iseob = iseob & ~bad
                islen = islen & ~bad
                okv = okv & ~bad

                # Far copies (past the resolve window, or sourcing at/after
                # a deferred destination) are recorded for the host pass;
                # their output bytes stay garbage and n_out skips ahead.
                far = islen & (
                    (dist > FAR_DIST)
                    | (n_out - dist + lenval > hole)
                )
                can_rec = farc < MAX_FAR
                okv = okv & (~far | can_rec)
                rec = far & can_rec
                fara = jnp.where(
                    (rows_F == farc) & rec, (n_out << 9) | lenval, fara
                )
                farb = jnp.where((rows_F == farc) & rec, dist, farb)
                hole = jnp.where(rec, jnp.minimum(hole, n_out), hole)
                farc = farc + rec.astype(jnp.int32)
                near = islen & ~far

                # Emits: exactly one byte per emitting lane this wave,
                # written into the ring at the lane's output cursor.
                byte = jnp.where(
                    in_copy, cb, jnp.where(in_stored, sb, sym & 0xFF)
                ).astype(jnp.uint32)
                emit = in_copy | in_stored | islit
                wrow = (n_out >> 2) & (RING_ROWS - 1)
                sh = (8 * (n_out & 3)).astype(jnp.uint32)
                onehot = (rows_RING == wrow) & emit
                cleared = rv & jnp.broadcast_to(
                    ~(jnp.uint32(0xFF) << sh).astype(jnp.int32), rv.shape
                )
                word_new = cleared | jnp.broadcast_to(
                    (byte << sh).astype(jnp.int32), rv.shape
                )
                ring_ref[:, :] = jnp.where(onehot, word_new, rv)

                n_out = (
                    n_out
                    + emit.astype(jnp.int32)
                    + jnp.where(rec, lenval, 0)
                )
                crem = jnp.where(
                    near, lenval, crem - in_copy.astype(jnp.int32)
                )
                cdist = jnp.where(near, dist, cdist)
                srem = srem - in_stored.astype(jnp.int32)
                cur = (
                    cur
                    + jnp.where(decode & ~bad, adv, 0)
                    + 8 * in_stored.astype(jnp.int32)
                )
                retire = iseob | (active & stored & (srem == 0))
                inblk = inblk & ~retire
                done = done | (retire & bfin)
                okv, done, inblk, stored, bfin = _masks(
                    okv, done, inblk, stored, bfin
                )
                return (it + 1, cur, n_out, okv, done, inblk, stored,
                        bfin, crem, cdist, srem, farc, hole, fara, farb)

            (wav, cur, n_out, okv, done, inblk, stored, bfin, crem,
             cdist, srem, farc, hole, fara, farb) = lax.while_loop(
                econd,
                ebody,
                (wav, cur, n_out, okv, done, inblk, stored, bfin, crem,
                 cdist, srem, farc, hole, fara, farb),
            )
            return (cur, n_out, okv, done, inblk, stored, bfin, crem,
                    cdist, srem, farc, hole, blk, tabs, fara, farb), wav

        # ---- per-step rounds: parse-if-needed, then emit ----------------
        def rcond(state):
            rnd, wav, c = state
            n_out = c[1]
            okv, done = _flags(c[2], c[3])
            progress = okv & ~done & (n_out < chunk_end)
            return (rnd < MAX_ROUNDS) & (wav < T_STEP) & jnp.any(progress)

        def rbody(state):
            rnd, wav, c = state
            okv, done, inblk = _flags(c[2], c[3], c[4])
            n_out = c[1]
            need = okv & ~done & ~inblk & (n_out < chunk_end)
            c = lax.cond(jnp.any(need), parse_fn, lambda x: x, c)
            c, wav = emit_phase(c, wav)
            return rnd + 1, wav, c

        carry0 = (cur0, n_out0, ok0, done0, inblk0, stored0, bfin0,
                  crem0, cdist0, srem0, farc0, hole0, blk0, tabs0,
                  fara0, farb0)
        _, _, c = lax.while_loop(
            rcond, rbody, (jnp.int32(0), jnp.int32(0), carry0)
        )
        (cur, n_out, okv, done, inblk, stored, bfin, crem, cdist, srem,
         farc, hole, blk, tabs, fara, farb) = c
        okv, done = _flags(okv, done)

        # A lane that still has chunk capacity after the round budget is
        # stuck (pathological stream): fail it rather than loop forever.
        stuck = okv & ~done & (n_out < chunk_end)
        okv = okv & ~stuck

        # ---- persist state, stream the finished tile out ----------------
        stw = jnp.zeros((_ST_ROWS, LANES), jnp.int32)

        def setreg(stw, r, v):
            return jnp.where(rows_st == r, jnp.broadcast_to(v, stw.shape),
                             stw)

        stw = setreg(stw, _R_CUR, cur)
        stw = setreg(stw, _R_NOUT, n_out)
        stw = setreg(stw, _R_OK, okv.astype(jnp.int32))
        stw = setreg(stw, _R_DONE, done.astype(jnp.int32))
        stw = setreg(stw, _R_INBLK, inblk)
        stw = setreg(stw, _R_STORED, stored)
        stw = setreg(stw, _R_BFINAL, bfin)
        stw = setreg(stw, _R_CREM, crem)
        stw = setreg(stw, _R_CDIST, cdist)
        stw = setreg(stw, _R_SREM, srem)
        stw = setreg(stw, _R_FARC, farc)
        stw = setreg(stw, _R_HOLE, hole)
        stw = setreg(stw, _R_BLK, blk)
        st_ref[:, :] = stw
        tabs_ref[:, :] = tabs
        fa_ref[:, :] = fara
        fb_ref[:, :] = farb

        start = (k * OC_ROWS) & (RING_ROWS - 1)
        out_ref[:, :] = ring_ref[pl.ds(start, OC_ROWS), :]
        nout_ref[:, :] = n_out
        ok_ref[:, :] = (okv & done & (n_out == isize)).astype(jnp.int32)
        farc_ref[:, :] = farc
        fara_ref[:, :] = fara
        farb_ref[:, :] = farb

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=(
        "r_words", "oc_rows", "ring_rows", "n_chunks", "t_step",
        "max_blocks", "max_far", "far_dist", "interpret",
    ),
)
def _inflate_lanes_kernel(
    streams, nbits, isizes, r_words: int, oc_rows: int, ring_rows: int,
    n_chunks: int, t_step: int, max_blocks: int, max_far: int,
    far_dist: int, interpret: bool,
):
    kernel = _kernel_factory(
        r_words, oc_rows, ring_rows, t_step, max_blocks, max_far, far_dist
    )
    return pl.pallas_call(
        kernel,
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec(
                (oc_rows, LANES), lambda k: (k, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, LANES), lambda k: (0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, LANES), lambda k: (0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, LANES), lambda k: (0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (max_far, LANES), lambda k: (0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (max_far, LANES), lambda k: (0, 0),
                memory_space=pltpu.VMEM,
            ),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n_chunks * oc_rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((1, LANES), jnp.int32),
            jax.ShapeDtypeStruct((1, LANES), jnp.int32),
            jax.ShapeDtypeStruct((1, LANES), jnp.int32),
            jax.ShapeDtypeStruct((max_far, LANES), jnp.int32),
            jax.ShapeDtypeStruct((max_far, LANES), jnp.int32),
        ),
        scratch_shapes=[
            pltpu.VMEM((ring_rows, LANES), jnp.int32),
            pltpu.VMEM((_ST_ROWS, LANES), jnp.int32),
            pltpu.VMEM((_TAB_ROWS, LANES), jnp.int32),
            pltpu.VMEM((max_far, LANES), jnp.int32),
            pltpu.VMEM((max_far, LANES), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
    )(streams, nbits, isizes)


def _apply_far_copies(
    lane_bytes: np.ndarray, fara: np.ndarray, farb: np.ndarray, n: int
) -> None:
    """Replay a lane's deferred far-distance copies in stream order.

    Events are recorded so that every source byte is either kernel-correct
    or patched by an earlier event, so an in-order byte loop (which also
    handles overlapping copies) reconstructs the exact LZ77 semantics."""
    for e in range(n):
        a = int(fara[e])
        dst, ln, dist = a >> 9, a & 511, int(farb[e])
        for k in range(ln):
            lane_bytes[dst + k] = lane_bytes[dst + k - dist]


@jax.jit
def _unpack_device(o: jax.Array) -> jax.Array:
    """[R,128] int32 word columns → [128, R*4] uint8 lane-major bytes
    (device-to-device; the on-chip output-residency view)."""
    bs = jnp.stack(
        [(o >> (8 * k)) & 0xFF for k in range(4)], axis=1
    ).astype(jnp.uint8)  # [R, 4, 128]
    return jnp.transpose(bs, (2, 0, 1)).reshape(o.shape[1], -1)


def inflate_lanes_ex(
    comp: np.ndarray,
    clens: np.ndarray,
    isizes: np.ndarray,
    max_blocks: int = 12,
    max_far: int = 64,
    far_dist: int = 1 << 15,
    chunk_bytes: int = _DEFAULT_CHUNK,
    interpret=None,
    keep_device: bool = False,
):
    """:func:`inflate_lanes` plus the on-chip output residency handoff.

    Returns ``(out, ok, dev)`` — ``dev`` is a device-resident uint8
    [B, out_cap] member-major byte view of the decoded payloads (member
    j's bytes at ``dev[j, :isizes[j]]``, across every 128-lane group of
    the call), or ``None`` whenever the view would not be byte-exact
    without host help: any member not decoded (``ok=0``), or any
    deferred far copy (host-replayed bytes are not in the device
    buffer)."""
    return _inflate_lanes_impl(
        comp, clens, isizes, max_blocks, max_far, far_dist, chunk_bytes,
        interpret, keep_device,
    )


def inflate_lanes(
    comp: np.ndarray,
    clens: np.ndarray,
    isizes: np.ndarray,
    max_blocks: int = 12,
    max_far: int = 64,
    far_dist: int = 1 << 15,
    chunk_bytes: int = _DEFAULT_CHUNK,
    interpret=None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Batched lockstep inflate of general DEFLATE members (any mix of
    stored/fixed/dynamic blocks), 128 members per kernel launch, output
    streamed chunk-by-chunk to HBM.

    ``comp`` uint8 [B, C] (rows zero-padded), ``clens``/``isizes`` int32
    [B].  Returns ``(out uint8 [B, max_isize], ok bool [B])`` — a member
    that is corrupt, exceeds ``max_blocks`` DEFLATE blocks, overflows the
    ``max_far`` far-copy budget, or whose *compressed* geometry exceeds
    the VMEM budget comes back ``ok=False`` and the caller tiers down to
    the XLA/host decoders.  Full 64 KiB BGZF members are inside the
    streaming geometry.  ``far_dist`` bounds the in-kernel LZ77 resolve
    ring; copies past it defer to the host-assisted replay pass (the
    default ring covers every legal DEFLATE distance, so the pass is
    exercised only by the windowed configuration).  ``chunk_bytes`` sets
    the per-lane output tile per grid step (power of two)."""
    out, ok_all, _ = _inflate_lanes_impl(
        comp, clens, isizes, max_blocks, max_far, far_dist, chunk_bytes,
        interpret, False,
    )
    return out, ok_all


def _inflate_lanes_impl(
    comp, clens, isizes, max_blocks, max_far, far_dist, chunk_bytes,
    interpret, keep_device,
):
    B, C = comp.shape
    if B == 0:
        return np.empty((0, 0), np.uint8), np.empty(0, bool), None
    max_out = int(isizes.max()) if len(isizes) else 0
    max_clen = int(clens.max()) if len(clens) else 0
    out = np.zeros((B, max_out), dtype=np.uint8)
    ok_all = np.zeros(B, dtype=bool)
    dev_groups = [] if keep_device else None
    geo = stream_geometry(
        max_clen, max_out, chunk_bytes, far_dist, max_far, max_blocks
    )
    if geo["vmem_bytes"] > _VMEM_BUDGET_BYTES or max_out > _MAX_ISIZE:
        return out, ok_all, None
    r_words = geo["r_words"]
    oc_rows = geo["oc_rows"]
    n_chunks = geo["n_chunks"]
    out_cap = n_chunks * oc_rows * 4
    interpret = pallas_interpret(interpret)
    for g0 in range(0, B, LANES):
        g1 = min(B, g0 + LANES)
        n = g1 - g0
        with span("flate.inflate.pack"):
            # Transpose the group: member j's words go down lane j.
            grp = np.zeros((r_words * 4, LANES), dtype=np.uint8)
            grp[:C, :n] = comp[g0:g1].T
            words = (
                grp.reshape(r_words, 4, LANES).astype(np.uint32)
                * (np.uint32(1) << (8 * np.arange(4, dtype=np.uint32)))[
                    None, :, None
                ]
            ).sum(axis=1).astype(np.uint32).view(np.int32)
            nbits = np.zeros((1, LANES), dtype=np.int32)
            nbits[0, :n] = clens[g0:g1] * 8
            isz = np.zeros((1, LANES), dtype=np.int32)
            isz[0, :n] = isizes[g0:g1]
            args = jnp.asarray(words), jnp.asarray(nbits), jnp.asarray(isz)
        with span("flate.inflate.launch"):
            o, nout, okk, farc, fara, farb = _inflate_lanes_kernel(
                *args, r_words, oc_rows, geo["ring_rows"], n_chunks,
                geo["t_step"], max_blocks, max_far, geo["far_dist"],
                bool(interpret),
            )
        with span("flate.inflate.wait"):
            by = np.asarray(o).view(np.uint32)
            nout = np.asarray(nout)[0]
            okk = np.asarray(okk)[0].astype(bool)
            farc = np.asarray(farc)[0]
            fara = np.asarray(fara)
            farb = np.asarray(farb)
        with span("flate.inflate.verify"):
            bytes_mat = np.zeros((out_cap, LANES), dtype=np.uint8)
            for k in range(4):
                bytes_mat[k::4] = ((by >> np.uint32(8 * k)) & 0xFF).astype(
                    np.uint8
                )
            for j in range(n):
                i = g0 + j
                okj = okk[j] and int(nout[j]) == int(isizes[i])
                ok_all[i] = okj
                if okj:
                    lane = bytes_mat[: isizes[i], j].copy()
                    if farc[j]:
                        _apply_far_copies(
                            lane, fara[:, j], farb[:, j], int(farc[j])
                        )
                    out[i, : isizes[i]] = lane
        if dev_groups is not None:
            if bool(ok_all[g0:g1].all()) and int(farc[:n].sum()) == 0:
                # On-chip output residency: this group's device byte view
                # is exact (no host-side far-copy patches).
                with span("flate.inflate.flatten"):
                    dev_groups.append(_unpack_device(o)[:n])
            else:
                dev_groups = None
    dev = None
    if dev_groups:
        # The caller can feed the device-parse chain without a d2h→h2d
        # bounce: one member-major view over every group.
        if len(dev_groups) == 1:
            dev = dev_groups[0]
        else:
            with span("flate.inflate.flatten"):
                dev = jnp.concatenate(dev_groups)
    return out, ok_all, dev
