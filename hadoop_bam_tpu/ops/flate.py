"""Device DEFLATE codec: batched BGZF inflate/deflate as TPU array programs.

The reference's compression layer is htsjdk's zlib behind
``BGZFCodec``/``BGZFCompressionOutputStream`` (util/BGZFCodec.java:33-63,
util/BGZFCompressionOutputStream.java) — native code on the host, one
stream at a time.  Here the hot loop is re-architected for a TPU: a batch
of BGZF members is decoded *in parallel as one array program* instead of
bit-serially.

Deflate (compress), device side
    Two tiers.  The top tier is the lockstep-lane Pallas **encoder**
    (ops/pallas/deflate_lanes.py): up to 128 members in the 128 vector
    lanes, each running a greedy hash-head LZ77 match-finder (4-byte
    hash, two-generation probe chain, min match 4) whose token stream a
    second lanes kernel bit-packs into fixed-Huffman DEFLATE — real
    compression, within ~1.05x of zlib level-1 on BAM-class data, wired
    into the part-write path (``deflate_blocks_device`` /
    ``io.bam.write_part_fast``) behind ``hadoopbam.deflate.lanes`` /
    ``HBAM_DEFLATE_LANES`` / the platform rule.

    The floor tier is literal-only fixed-Huffman DEFLATE (btype=01):
    every input byte maps to an 8- or 9-bit code independently, so the
    whole emit is a prefix sum over code lengths plus a per-output-bit
    searchsorted — embarrassingly parallel, MXU-free but VPU/HBM
    friendly.  "Fixed Huffman is enough for validity" (SURVEY.md §7
    stage 6); ratio is traded for zero serial device work.  ``level=0``
    bypasses both and emits stored blocks (uncompressed parts).

Inflate (decompress), device side
    DEFLATE decode looks inherently bit-serial (each Huffman codeword's
    start depends on the previous).  The TPU formulation is the two-pass
    speculative scheme (SURVEY.md §7 "hard parts" mitigation):

    1. *Speculative symbol resolve*: for EVERY bit position p, decode the
       token that WOULD start at p (one 512-entry table gather + a few
       arithmetic ops), yielding next[p] (where the following token would
       start), emit[p] (bytes it would produce) and its payload.
    2. *Chain marking by pointer doubling*: the true token sequence is
       the orbit of bit 3 (after the block header) under ``next``.
       log2(nbits) rounds of ``reach |= scatter(reach, jump);
       jump = jump[jump]`` mark it — O(n log n) work, all gathers/
       scatters, no data-dependent control flow.
    3. *Parallel LZ77 copy resolve*: output offsets are a prefix sum of
       on-chain emits; every output byte's source is either a literal
       token or a strictly-earlier output position (for overlapping
       copies, ``src = o - d + (j - o) mod d``), so log2(out) rounds of
       pointer-jumping materialize all back-references.

    Three kernels share the machinery: ``inflate_fixed`` (all-fixed
    members, one launch), ``inflate_stored`` (zlib level 0), and
    ``inflate_dynamic`` — the general decoder that builds canonical
    Huffman tables ON DEVICE per member per block (code-length RLE via a
    short ``lax.scan``, counts→first-codes→argsort ranks all dense) and
    walks any per-member mix of stored/fixed/dynamic blocks in a
    block-sequential outer loop, so real zlib output (level ≥1 emits
    dynamic blocks) decodes on device instead of tiering to the host.
    Members that fail any device check still tier down to native zlib in
    the ``bgzf_decompress_device`` wrapper — the same fallback stance as
    the split planner's index→guesser chain.

Host-side helpers assemble/validate the BGZF framing (headers, CRC32,
ISIZE — spec/bgzf.py owns the layout) around the device payloads.

Performance status (v5e-1, measured): these XLA kernels bottleneck on
XLA:TPU gather throughput (~70M gathered elements/s) — roughly 0.5-1 MB/s
end to end, far below the native host tier (~170 MB/s zlib).  They are
the correctness floor and the universal device fallback; the hot path is
the lockstep-lane Pallas tier below.

Device codec tiers, top to bottom (each tier falls through per member):

1. **LIVE — lockstep lanes, general** (ops/pallas/inflate_lanes.py):
   128 BGZF members ride the 128 vector lanes of one Pallas kernel —
   per-member canonical Huffman tables built on chip, transposed-stream
   bit windows, 15-compare canonical decode, byte-per-wave lockstep
   emit, windowed LZ77 resolve with a host-assisted pass for rare
   far-distance copies, and per-member ``[n_out, ok]`` meta so one bad
   member tiers down without dooming its launch.  Built on the walk
   engine of ops/pallas/inflate_probe.py (chip speed not measured yet).
   Gated
   by the ``hadoopbam.inflate.lanes`` conf key / ``HBAM_INFLATE_LANES``
   env var, defaulting to the same platform rule as the
   device-resident parse (:func:`lanes_tier_enabled`).
2. **LIVE — lockstep lanes, literal-only fixed**
   (ops/pallas/inflate_fixed.py): the specialized slice for what
   :func:`deflate_fixed` emits; preferred for the "fixed" group on real
   chips when the general tier is off.
3. **XLA array programs** (this module): ``inflate_stored`` /
   ``inflate_fixed`` / ``inflate_dynamic`` — slow but fully general and
   platform-agnostic.
4. **Native host zlib** (spec/bgzf.py + native/): the unconditional
   correctness tier; nothing above it is load-bearing for correctness.

Both lanes kernels are HBM-STREAMING: they grid over fixed-size chunks
(output chunks for the decoder, input chunks for the encoder) with
per-lane state — bit cursors, canonical tables, a 32 KiB LZ77 resolve
ring, hash-head chains, token tiles — carried across grid steps in VMEM
scratch, so full 64 KiB BGZF members ride the lanes tiers instead of
tiering down at a whole-member-VMEM cap.  On-chip output residency is
wired too: ``inflate_blocks_device(return_device=True)`` leaves the
inflated split in HBM and the device-parse chain kernel consumes it
without the d2h/h2d bounce (``RecordBatch.device_data``).

Caveat for all launches: XLA:TPU gathers silently mis-index above 2^24
elements per launch (f32 index precision); wrappers chunk accordingly.
"""

from __future__ import annotations

import os
import struct
import zlib
from functools import partial
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import faults
from ..spec import bgzf
from ..utils.tracing import span, stage as _trace_stage

# --------------------------------------------------------------------------
# Fixed-Huffman tables (RFC 1951 §3.2.5-3.2.6), precomputed as numpy consts.
# --------------------------------------------------------------------------

LEN_BASE = np.array(
    [3, 4, 5, 6, 7, 8, 9, 10, 11, 13, 15, 17, 19, 23, 27, 31, 35, 43, 51,
     59, 67, 83, 99, 115, 131, 163, 195, 227, 258], dtype=np.int32)
LEN_EXTRA = np.array(
    [0, 0, 0, 0, 0, 0, 0, 0, 1, 1, 1, 1, 2, 2, 2, 2, 3, 3, 3, 3, 4, 4, 4,
     4, 5, 5, 5, 5, 0], dtype=np.int32)
DIST_BASE = np.array(
    [1, 2, 3, 4, 5, 7, 9, 13, 17, 25, 33, 49, 65, 97, 129, 193, 257, 385,
     513, 769, 1025, 1537, 2049, 3073, 4097, 6145, 8193, 12289, 16385,
     24577], dtype=np.int32)
DIST_EXTRA = np.array(
    [0, 0, 0, 0, 1, 1, 2, 2, 3, 3, 4, 4, 5, 5, 6, 6, 7, 7, 8, 8, 9, 9, 10,
     10, 11, 11, 12, 12, 13, 13], dtype=np.int32)


def _bit_reverse(v: int, n: int) -> int:
    r = 0
    for _ in range(n):
        r = (r << 1) | (v & 1)
        v >>= 1
    return r


def _fixed_code(sym: int) -> Tuple[int, int]:
    """(code, nbits) of a fixed-Huffman litlen symbol (MSB-first code)."""
    if sym <= 143:
        return 0x30 + sym, 8
    if sym <= 255:
        return 0x190 + (sym - 144), 9
    if sym <= 279:
        return sym - 256, 7
    return 0xC0 + (sym - 280), 8


def _build_litlen_table() -> np.ndarray:
    """512-entry stream-order lookup: next-9-bits → (sym<<4 | codelen)."""
    table = np.full(512, (287 << 4) | 8, dtype=np.int32)  # default: invalid
    for sym in range(288):
        code, n = _fixed_code(sym)
        rev = _bit_reverse(code, n)
        for free in range(1 << (9 - n)):
            table[rev | (free << n)] = (sym << 4) | n
    return table


def _build_dist_table() -> np.ndarray:
    """32-entry stream-order lookup: next-5-bits → distance symbol."""
    table = np.zeros(32, dtype=np.int32)
    for dsym in range(32):
        table[_bit_reverse(dsym, 5)] = dsym
    return table


LITLEN_TABLE = _build_litlen_table()
DIST_TABLE = _build_dist_table()

# Fixed-Huffman code lengths (RFC 1951 §3.2.6) — the btype=01 table is just
# a particular code-length vector, so the dynamic decoder subsumes it.
FIXED_LITLEN_LENS = np.array(
    [8] * 144 + [9] * 112 + [7] * 24 + [8] * 8, dtype=np.int32
)
FIXED_DIST_LENS = np.array([5] * 32, dtype=np.int32)
# Order in which code-length-code lengths appear in a dynamic header.
CLC_ORDER = np.array(
    [16, 17, 18, 0, 8, 7, 9, 6, 10, 5, 11, 4, 12, 3, 13, 2, 14, 1, 15],
    dtype=np.int32,
)
REV8 = np.array([_bit_reverse(i, 8) for i in range(256)], dtype=np.int32)

# Worst case the literal-only emit expands 9/8 + header; cap the per-member
# payload so a device-deflated block always fits the u16 BSIZE field.
DEV_MAX_PAYLOAD = 0xDF00  # 57088 → ≤ 64252-byte block, < 0x10000
# Default block payload for the device deflate: sized so every emitted
# member fits the lockstep Pallas decoder's whole-member-in-VMEM budget
# (ops/pallas/inflate_fixed.py) — device-compressed BGZF then decodes
# entirely on the Pallas tier.  Literal-only emit has no cross-block
# matches, so smaller blocks cost only the ~26-byte header per block
# (~0.1% at this size), not compression ratio.
DEV_DEFAULT_PAYLOAD = 24000

# Member payload for the lockstep-lane LZ77 encoder tier
# (ops/pallas/deflate_lanes.py).  The streaming geometry (token tiles
# chunked out to HBM, persistent hash heads) lifted the old 4 KiB
# whole-member-VMEM cap, so the lanes tier now emits full-size members:
# DEV_MAX_PAYLOAD is the largest payload whose worst-case (all-literal)
# fixed-Huffman emit still fits the u16 BSIZE field — the same blocking
# real BGZF writers target.
DEV_LZ_PAYLOAD = DEV_MAX_PAYLOAD

# XLA:TPU gathers mis-index when a single launch exceeds 2^24 elements
# (observed empirically: B*NB == 2^24 exact, 2^24+… corrupt — consistent
# with an f32-precision index path).  Keep every launch safely below.
_MAX_LAUNCH_ELEMS = 1 << 23


# --------------------------------------------------------------------------
# Host reference encoder (token-level) — the test oracle's writing half.
# --------------------------------------------------------------------------


class _BitWriter:
    def __init__(self) -> None:
        self.buf = bytearray()
        self.acc = 0
        self.n = 0

    def bits_lsb(self, value: int, n: int) -> None:
        """n bits of value, LSB first (extra-bits fields, headers)."""
        self.acc |= (value & ((1 << n) - 1)) << self.n
        self.n += n
        while self.n >= 8:
            self.buf.append(self.acc & 0xFF)
            self.acc >>= 8
            self.n -= 8

    def code_msb(self, code: int, n: int) -> None:
        """A Huffman codeword: MSB of the code enters the stream first."""
        for i in range(n - 1, -1, -1):
            self.bits_lsb((code >> i) & 1, 1)

    def done(self) -> bytes:
        if self.n:
            self.buf.append(self.acc & 0xFF)
            self.acc = 0
            self.n = 0
        return bytes(self.buf)


def encode_tokens_fixed(tokens: Sequence, final: bool = True) -> bytes:
    """Encode an explicit token list as fixed-Huffman DEFLATE (host oracle).

    Tokens: ``("lit", byte)``, ``("copy", length, dist)``, or ``("block",)``
    to close the current block (non-final) and open a new fixed block —
    precise control for exercising the device decoder's edge cases.
    """
    w = _BitWriter()

    def open_block(is_final: bool) -> None:
        w.bits_lsb(1 if is_final else 0, 1)
        w.bits_lsb(1, 2)  # btype=01 fixed

    blocks: List[List] = [[]]
    for t in tokens:
        if t[0] == "block":
            blocks.append([])
        else:
            blocks[-1].append(t)
    for bi, blk in enumerate(blocks):
        open_block(final and bi == len(blocks) - 1)
        for t in blk:
            if t[0] == "lit":
                code, n = _fixed_code(t[1])
                w.code_msb(code, n)
            else:
                _, length, dist = t
                li = int(np.searchsorted(LEN_BASE, length, side="right")) - 1
                if LEN_BASE[li] + (1 << LEN_EXTRA[li]) <= length:
                    li += 1
                code, n = _fixed_code(257 + li)
                w.code_msb(code, n)
                w.bits_lsb(length - int(LEN_BASE[li]), int(LEN_EXTRA[li]))
                di = int(np.searchsorted(DIST_BASE, dist, side="right")) - 1
                w.code_msb(di, 5)
                w.bits_lsb(dist - int(DIST_BASE[di]), int(DIST_EXTRA[di]))
        code, n = _fixed_code(256)
        w.code_msb(code, n)
    return w.done()


# --------------------------------------------------------------------------
# Device deflate: literal-only fixed-Huffman emit.
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnums=(2,))
def deflate_fixed(
    payload: jax.Array, lens: jax.Array, out_bytes: int
) -> Tuple[jax.Array, jax.Array]:
    """Batched literal-only fixed-Huffman DEFLATE.

    ``payload``: uint8 [B, P] (rows padded), ``lens``: int32 [B] valid
    lengths, ``out_bytes``: static output width (≥ (3+9P+7+7)//8).
    Returns (comp uint8 [B, out_bytes], clens int32 [B]).
    """
    B, P = payload.shape
    b = payload.astype(jnp.int32)
    i = jnp.arange(P, dtype=jnp.int32)[None, :]
    valid = i < lens[:, None]
    hi = b >= 144
    code = jnp.where(hi, 0x190 + (b - 144), 0x30 + b)
    clen = jnp.where(valid, jnp.where(hi, 9, 8), 0)
    # Bit offset of each byte's codeword: 3 header bits + running emit.
    cum = jnp.cumsum(clen, axis=1)
    off = 3 + cum - clen
    nbits_total = 3 + cum[:, -1] + 7  # + EOB (7 zero bits)
    NB = out_bytes * 8
    # Gather-only emit (TPU scatters are pathologically slow): for every
    # output bit position, searchsorted finds the codeword covering it —
    # codewords are contiguous, so bit j belongs to the code whose offset
    # interval contains j.
    j = jnp.arange(NB, dtype=jnp.int32)[None, :]
    ends = cum + 3  # end bit (exclusive) of each codeword
    src = jax.vmap(partial(jnp.searchsorted, side="right"))(
        ends, jnp.broadcast_to(j, (B, NB))
    ).astype(jnp.int32)
    src_c = jnp.clip(src, 0, P - 1)
    code_j = jnp.take_along_axis(code, src_c, axis=1)
    clen_j = jnp.take_along_axis(clen, src_c, axis=1)
    off_j = jnp.take_along_axis(off, src_c, axis=1)
    k = j - off_j  # bit index within the codeword, MSB first
    in_code = (src < P) & (k >= 0) & (k < clen_j)
    bit = jnp.where(
        in_code, (code_j >> jnp.maximum(clen_j - 1 - k, 0)) & 1, 0
    )
    # Header bits 0b011 at positions 0-1 (bfinal=1, btype=01).
    bit = jnp.where(j < 2, 1, bit).astype(jnp.uint8)
    weights = (1 << jnp.arange(8, dtype=jnp.uint8)).astype(jnp.uint8)
    comp = (
        (bit.reshape(B, out_bytes, 8) * weights[None, None, :])
        .sum(axis=2)
        .astype(jnp.uint8)
    )
    clens = (nbits_total + 7) // 8
    return comp, clens


# --------------------------------------------------------------------------
# Device inflate: speculative decode + pointer doubling + parallel copies.
# --------------------------------------------------------------------------


def _token_tables():
    return (
        jnp.asarray(LITLEN_TABLE),
        jnp.asarray(DIST_TABLE),
        jnp.asarray(LEN_BASE),
        jnp.asarray(LEN_EXTRA),
        jnp.asarray(DIST_BASE),
        jnp.asarray(DIST_EXTRA),
    )


# --------------------------------------------------------------------------
# Machinery shared by the inflate kernels (fixed / dynamic): bit-window
# reads, the pointer-doubling chain walk, token→output coverage, and the
# member-wide LZ77 copy resolution.
# --------------------------------------------------------------------------


def _bit_window_fn(comp: jax.Array, pad: int = 8):
    """Returns ``window(bitpos) -> uint32`` reading 32 stream bits at any
    per-member bit offset (bitpos broadcastable to [B, ...])."""
    B = comp.shape[0]
    data = jnp.pad(comp, ((0, 0), (0, pad))).astype(jnp.uint32)

    def window(bitpos):
        bp = jnp.broadcast_to(bitpos, (B,) + bitpos.shape[1:])
        flat = bp.reshape(B, -1)
        bi = flat >> 3
        s = (flat & 7).astype(jnp.uint32)
        b0 = jnp.take_along_axis(data, bi, axis=1)
        b1 = jnp.take_along_axis(data, bi + 1, axis=1)
        b2 = jnp.take_along_axis(data, bi + 2, axis=1)
        b3 = jnp.take_along_axis(data, bi + 3, axis=1)
        w = b0 | (b1 << 8) | (b2 << 16) | (b3 << 24)
        return (w >> s).reshape(bp.shape)

    return window


def _chain_walk(nxt: jax.Array, start: jax.Array, T: int) -> jax.Array:
    """Enumerate ``T`` chain positions from ``start`` through the jump map
    ``nxt`` (gather-only pointer doubling; terminal tokens self-loop so
    slots past the chain end stall there).  ``start``: int32 [B]."""
    B, NB = nxt.shape
    t = jnp.arange(T, dtype=jnp.int32)
    cur = jnp.broadcast_to(
        jnp.clip(start, 0, NB - 1)[:, None], (B, T)
    )
    jump = nxt
    for k in range(max(1, int(T - 1).bit_length())):
        stepped = jnp.take_along_axis(jump, cur, axis=1)
        cur = jnp.where(((t >> k) & 1)[None, :] == 1, stepped, cur)
        jump = jnp.take_along_axis(jump, jump, axis=1)
    return cur


def _coverage(cum_out: jax.Array, jj: jax.Array, T: int) -> jax.Array:
    """Index of the chain slot covering each output position: output byte
    ``jj`` belongs to the first token whose cumulative emit exceeds it
    (cum_out is sorted — a batched binary search)."""
    B = cum_out.shape[0]
    cov = jax.vmap(partial(jnp.searchsorted, side="right"))(
        cum_out, jnp.broadcast_to(jj, (B,) + jj.shape[1:])
    ).astype(jnp.int32)
    return jnp.clip(cov, 0, T - 1)


def _lz77_resolve(lit_j, val_j, d_j, o_j, covered, j):
    """Materialize all LZ77 copies with log-rounds pointer jumping.
    Returns (out uint8, neg_src bool[B]) — ``neg_src`` flags copies
    reaching before the stream start (invalid)."""
    OUT = j.shape[1]
    src = jnp.where(
        lit_j | ~covered, j, o_j - d_j + ((j - o_j) % d_j)
    )
    neg = jnp.any(covered & (src < 0), axis=1)
    src = jnp.clip(src, 0, OUT - 1)
    val0 = jnp.where(lit_j, val_j, 0).astype(jnp.uint8)
    ptr = src
    for _ in range(max(1, int(OUT - 1).bit_length())):
        ptr = jnp.take_along_axis(ptr, ptr, axis=1)
    out = jnp.take_along_axis(val0, ptr, axis=1)
    return jnp.where(covered, out, 0), neg


@partial(jax.jit, static_argnums=(3, 4))
def inflate_fixed(
    comp: jax.Array,
    clens: jax.Array,
    isizes: jax.Array,
    out_bytes: int,
    max_cbits: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Batched inflate of all-fixed-Huffman DEFLATE members.

    ``comp``: uint8 [B, C]; ``clens``/``isizes``: int32 [B];
    ``out_bytes``: static output width (≥ max isize); ``max_cbits``: static
    bound on real compressed bits per member (defaults to the padded C*8 —
    callers pass the batch max so the chain-walk slot budget tracks real
    stream size, not the pow2 bucket).
    Returns (out uint8 [B, out_bytes], ok bool [B]).
    """
    B, C = comp.shape
    litlen_t, dist_t, len_base, len_extra, dist_base, dist_extra = (
        _token_tables()
    )
    NB = C * 8
    window = _bit_window_fn(comp)
    p = jnp.arange(NB, dtype=jnp.int32)[None, :]

    w = window(p)
    t = litlen_t[(w & 511).astype(jnp.int32)]
    sym = t >> 4
    L = t & 15
    islit = sym < 256
    iseob = sym == 256
    islen = (sym > 256) & (sym < 286)
    bad = sym >= 286
    li = jnp.clip(sym - 257, 0, 28)
    lext = len_extra[li]
    lenval = len_base[li] + ((w >> L.astype(jnp.uint32)).astype(jnp.int32)
                             & ((1 << lext) - 1))
    # Distance field starts after the length code + extra bits.
    pd = p + L + lext
    wd = window(pd)
    dsym = dist_t[(wd & 31).astype(jnp.int32)]
    bad = bad | (islen & (dsym >= 30))
    dsym = jnp.clip(dsym, 0, 29)
    dext = dist_extra[dsym]
    dist = dist_base[dsym] + ((wd >> 5).astype(jnp.int32) & ((1 << dext) - 1))
    # EOB: terminal iff its code ends inside the final byte's bit padding
    # (bfinal lives in the block *header*, which a mid-stream position
    # can't see; the byte-boundary test is equivalent because a non-final
    # EOB is always followed by ≥10 more payload bits).  A non-final EOB
    # chains straight into the next 3-bit header, which must announce
    # another fixed block (btype=01).
    nbits_real = clens[:, None] * 8
    term = iseob & (p + 15 > nbits_real)
    hdr3 = ((w >> L.astype(jnp.uint32)) & 7).astype(jnp.int32)
    next_fixed = ((hdr3 >> 1) & 3) == 1
    bad = bad | (iseob & ~term & ~next_fixed)
    adv = jnp.where(
        islit,
        L,
        jnp.where(iseob, L + 3, L + lext + 5 + dext),
    )
    nxt = jnp.where(term, p, jnp.minimum(p + adv, NB - 1))
    emit = jnp.where(islit, 1, jnp.where(islen, lenval, 0))
    # A token must end inside the member's compressed bytes.
    overrun = (~term) & ((p + adv) > nbits_real)
    bad = bad | overrun
    emit = jnp.where(bad, 0, emit)

    # Chain enumeration, gather-only (TPU scatters are pathologically
    # slow): token t's bit position is advance(3, t); binary-decompose t
    # while doubling the jump map — jump composition along a chain is
    # additive, so bits can be applied in any order.  The terminal EOB is
    # a self-loop, so slots past the end of the chain stall there (emit 0).
    # Slot budget: every emitting token produces ≥1 byte (≤ out_bytes of
    # them) and every extra block costs ≥10 bits of stream (3-bit header +
    # 7-bit EOB), so the EOB count is bounded by real-bits//10 — no fixed
    # 64-block cap (ADVICE r1: many tiny blocks previously overflowed the
    # walk).
    real_bits = NB if max_cbits is None else min(NB, max_cbits)
    T = out_bytes + real_bits // 10 + 8
    cur = _chain_walk(nxt, jnp.full((B,), 3, jnp.int32), T)

    bad_t = jnp.take_along_axis(bad, cur, axis=1)
    term_t = jnp.take_along_axis(term, cur, axis=1)
    ok = ~jnp.any(bad_t, axis=1) & term_t[:, -1]  # must reach a final EOB
    emit_t = jnp.take_along_axis(emit, cur, axis=1)
    cum_out = jnp.cumsum(emit_t, axis=1)
    out_off_t = cum_out - emit_t
    total = cum_out[:, -1]
    ok = ok & (total == isizes) & (total <= out_bytes)

    # Output coverage + member-wide LZ77 resolution (shared machinery).
    OUT = out_bytes
    j = jnp.arange(OUT, dtype=jnp.int32)[None, :]
    cov = _coverage(cum_out, j, T)
    tp = jnp.take_along_axis(cur, cov, axis=1)  # bit pos of covering token
    covered = j < total[:, None]
    lit_j = jnp.take_along_axis(islit, tp, axis=1) & covered
    sym_j = jnp.take_along_axis(sym, tp, axis=1)
    d_j = jnp.maximum(jnp.take_along_axis(dist, tp, axis=1), 1)
    o_j = jnp.take_along_axis(out_off_t, cov, axis=1)
    out, neg = _lz77_resolve(lit_j, sym_j, d_j, o_j, covered, j)
    ok = ok & ~neg
    return out, ok


_MAX_STORED_BLOCKS = 8  # zlib level-0 emits ≤3 for a ≤64KB member


@partial(jax.jit, static_argnums=(3,))
def inflate_stored(
    comp: jax.Array, clens: jax.Array, isizes: jax.Array, out_bytes: int
) -> Tuple[jax.Array, jax.Array]:
    """Stored-block members (zlib level 0): a short chain of
    [3-bit header | pad-to-byte | LEN NLEN | raw] blocks per member,
    walked in lock-step across the batch."""
    B, C = comp.shape
    pad = jnp.pad(comp, ((0, 0), (0, 5))).astype(jnp.int32)
    j = jnp.arange(out_bytes, dtype=jnp.int32)[None, :]
    out0 = jnp.zeros((B, out_bytes), dtype=jnp.uint8)
    state = (
        jnp.zeros(B, jnp.int32),  # byte pos in comp
        jnp.zeros(B, jnp.int32),  # bytes emitted
        jnp.ones(B, bool),  # ok so far
        jnp.zeros(B, bool),  # saw bfinal
        out0,
    )

    def step(_, st):
        pos, outp, ok, done, out = st
        hdr = jnp.take_along_axis(pad, pos[:, None], axis=1)[:, 0] & 7
        b1 = jnp.take_along_axis(pad, pos[:, None] + 1, axis=1)[:, 0]
        b2 = jnp.take_along_axis(pad, pos[:, None] + 2, axis=1)[:, 0]
        b3 = jnp.take_along_axis(pad, pos[:, None] + 3, axis=1)[:, 0]
        b4 = jnp.take_along_axis(pad, pos[:, None] + 4, axis=1)[:, 0]
        ln = b1 | (b2 << 8)
        nln = b3 | (b4 << 8)
        live = ~done & ok
        good = ((hdr & 6) == 0) & (ln == (nln ^ 0xFFFF)) & (
            pos + 5 + ln <= clens
        )
        ok = jnp.where(live, good, ok)
        src = pos[:, None] + 5 + (j - outp[:, None])
        mask = live[:, None] & (j >= outp[:, None]) & (
            j < outp[:, None] + ln[:, None]
        )
        vals = jnp.take_along_axis(
            pad, jnp.clip(src, 0, C + 4), axis=1
        ).astype(jnp.uint8)
        out = jnp.where(mask, vals, out)
        done = done | (live & ((hdr & 1) == 1))
        pos = jnp.where(live, pos + 5 + ln, pos)
        outp = jnp.where(live, outp + ln, outp)
        return pos, outp, ok, done, out

    pos, outp, ok, done, out = jax.lax.fori_loop(
        0, _MAX_STORED_BLOCKS, step, state
    )
    ok = ok & done & (outp == isizes) & (isizes <= out_bytes)
    out = jnp.where(j < isizes[:, None], out, 0)
    return out, ok


# --------------------------------------------------------------------------
# Dynamic-Huffman device inflate (VERDICT r2: real zlib output must decode
# on device, not tier straight to the host).
#
# Architecture: a block-sequential outer loop (static unroll, lock-step
# across the batch) whose every iteration decodes ONE DEFLATE block per
# member — any mix of stored/fixed/dynamic across members and across
# blocks.  Per iteration:
#   1. parse the block header; for btype=10 run the code-length RLE section
#      through a short lax.scan (≤318 steps) and build the member's
#      canonical litlen/dist decoders ON DEVICE (counts → first codes →
#      argsort symbol ranks — all dense);
#   2. speculative token resolve at every bit position using canonical
#      decode (15 unrolled range compares + one ≤288-entry gather — no
#      2^15 LUT per member);
#   3. chain-walk from the block's first data bit (pointer doubling); the
#      EOB is a self-loop so the walk terminates exactly at block end;
#   4. merge the block's literal/copy coverage into member-wide val/src
#      planes, advance the bit cursor past the EOB into the next header.
# A single member-wide LZ77 pointer-jump pass then materializes all copies
# (back-references legally span blocks).
# --------------------------------------------------------------------------


def _canonical_decoder(lens: jax.Array, max_len: int):
    """Canonical-Huffman decode tables from per-symbol code lengths.

    ``lens``: int32 [B, S] (0 = symbol unused).  Returns
    ``(first, count, symoff, sym_sorted)`` with shapes [B, max_len+1]×3 and
    [B, S]: a code of length L and MSB-first value c maps to symbol
    ``sym_sorted[symoff[L] + c - first[L]]`` iff
    ``first[L] <= c < first[L]+count[L]`` (RFC 1951 §3.2.2).
    """
    B, S = lens.shape
    Lr = jnp.arange(max_len + 1, dtype=jnp.int32)
    count = jnp.sum(
        (lens[:, None, :] == Lr[None, :, None]) & (Lr[None, :, None] > 0),
        axis=2,
        dtype=jnp.int32,
    )
    firsts = [jnp.zeros((B,), jnp.int32)]
    code = jnp.zeros((B,), jnp.int32)
    for L in range(1, max_len + 1):
        code = (code + count[:, L - 1]) << 1
        firsts.append(code)
    first = jnp.stack(firsts, axis=1)
    symoff = jnp.cumsum(count, axis=1) - count
    key = jnp.where(
        lens > 0,
        lens * (2 * S) + jnp.arange(S, dtype=jnp.int32)[None, :],
        jnp.int32(1 << 24),
    )
    sym_sorted = jnp.argsort(key, axis=1).astype(jnp.int32)
    return first, count, symoff, sym_sorted


def _kraft_valid(
    count: jax.Array, max_len: int, allow_single: bool = True
) -> jax.Array:
    """Per-member validity of a canonical table's length histogram
    (ADVICE r2 low).  ``count``: int32 [B, max_len+1], as returned in
    ``_canonical_decoder``'s tables[1].

    Over-subscribed sets (Kraft sum > 1) can alias two symbols onto one
    window and ``_canon_decode``'s smallest-length-wins rule would silently
    pick one — so reject them.  Incomplete sets are rejected too, except —
    matching zlib's inftrees.c — a single code of length 1 when
    ``allow_single`` (some encoders emit a lone distance code; zlib never
    extends this grace to the code-length table).  Empty sets are valid
    here; whether an empty table may be *used* is enforced at decode
    time."""
    Lr = jnp.arange(max_len + 1, dtype=jnp.int32)
    kraft = jnp.sum(count << (max_len - Lr)[None, :], axis=1)
    ncodes = jnp.sum(count, axis=1)
    full = jnp.int32(1) << max_len
    ok = (ncodes == 0) | (kraft == full)
    if allow_single:
        ok = ok | ((ncodes == 1) & (count[:, 1] == 1))
    return ok


def _canon_decode(rev: jax.Array, tables, max_len: int):
    """Decode MSB-first-reversed bit windows against canonical tables.

    ``rev``: int32 [...], the next ``max_len`` stream bits with the first
    stream bit in the MSB.  Returns (sym, L, matched); garbage positions
    (speculative) may be unmatched."""
    first, count, symoff, sym_sorted = tables
    expand = (1,) * (rev.ndim - 1)
    Lsel = jnp.full(rev.shape, 99, dtype=jnp.int32)
    for L in range(max_len, 0, -1):  # downward: smallest L wins last
        cand = rev >> (max_len - L)
        f = first[:, L].reshape(-1, *expand)
        c = count[:, L].reshape(-1, *expand)
        match = (cand >= f) & (cand < f + c)
        Lsel = jnp.where(match, L, Lsel)
    matched = Lsel < 99
    Ls = jnp.where(matched, Lsel, 1)
    cand = rev >> (max_len - Ls)
    f_s = jnp.take_along_axis(
        first, Ls.reshape(first.shape[0], -1), axis=1
    ).reshape(Ls.shape)
    o_s = jnp.take_along_axis(
        symoff, Ls.reshape(symoff.shape[0], -1), axis=1
    ).reshape(Ls.shape)
    idx = jnp.clip(o_s + cand - f_s, 0, sym_sorted.shape[1] - 1)
    sym = jnp.take_along_axis(
        sym_sorted, idx.reshape(sym_sorted.shape[0], -1), axis=1
    ).reshape(Ls.shape)
    return sym, Ls, matched


_MAX_HDR_TOKENS = 318  # ≤286+30+2 RLE tokens fill the code-length section


@partial(jax.jit, static_argnums=(3, 4))
def inflate_dynamic(
    comp: jax.Array,
    clens: jax.Array,
    isizes: jax.Array,
    out_bytes: int,
    max_blocks: int = 8,
) -> Tuple[jax.Array, jax.Array]:
    """Batched inflate of general DEFLATE members (dynamic/fixed/stored
    blocks in any per-member mix), tables built on device.

    ``comp``: uint8 [B, C]; ``clens``/``isizes``: int32 [B]; ``out_bytes``
    static ≥ max isize; ``max_blocks`` static bound on DEFLATE blocks per
    member (zlib's 16K-symbol block buffer means a 64KiB BGZF payload has
    ≤5; members exceeding the bound fail cleanly → host tier).
    Returns (out uint8 [B, out_bytes], ok bool [B]).
    """
    B, C = comp.shape
    NB = C * 8
    OUT = out_bytes
    _, _, len_base, len_extra, dist_base, dist_extra = _token_tables()
    rev8 = jnp.asarray(REV8)
    clc_order = jnp.asarray(CLC_ORDER)
    fixed_ll = jnp.asarray(FIXED_LITLEN_LENS)
    fixed_dl = jnp.asarray(FIXED_DIST_LENS)

    nbits_real = clens * 8
    window = _bit_window_fn(comp)
    bytes_pad = jnp.pad(comp, ((0, 0), (0, 8)))  # stored-block raw copies

    def rev15(w):
        v = (w & 0x7FFF).astype(jnp.int32)
        r16 = (rev8[v & 0xFF] << 8) | rev8[v >> 8]
        return r16 >> 1

    p = jnp.arange(NB, dtype=jnp.int32)[None, :]
    j = jnp.arange(OUT, dtype=jnp.int32)[None, :]

    # Member-wide output planes, merged block by block.
    lit_plane = jnp.zeros((B, OUT), bool)
    val_plane = jnp.zeros((B, OUT), jnp.uint8)
    dst_plane = jnp.ones((B, OUT), jnp.int32)
    off_plane = jnp.zeros((B, OUT), jnp.int32)  # token output offset

    bitpos = jnp.zeros((B,), jnp.int32)
    out_base = jnp.zeros((B,), jnp.int32)
    ok = jnp.ones((B,), bool)
    done = jnp.zeros((B,), bool)

    T = OUT + 2  # per-block chain slots: every emitting token emits ≥1 byte

    def _block_step(carry):
        """Decode ONE DEFLATE block per still-live member."""
        (bitpos, out_base, ok, done,
         lit_plane, val_plane, dst_plane, off_plane) = carry
        live = ok & ~done
        hdr = window(bitpos[:, None])[:, 0]
        bfinal = (hdr & 1) == 1
        btype = ((hdr >> 1) & 3).astype(jnp.int32)
        ok = ok & (~live | (btype != 3))

        # ---- stored block (btype=00): byte-aligned raw copy ------------
        st_bit = (bitpos + 3 + 7) & ~7
        sb = st_bit >> 3
        ln_w = window((sb << 3)[:, None])[:, 0]
        s_len = (ln_w & 0xFFFF).astype(jnp.int32)
        s_nlen = ((ln_w >> 16) & 0xFFFF).astype(jnp.int32)
        stored = live & (btype == 0)
        ok = ok & (
            ~stored
            | ((s_len == (s_nlen ^ 0xFFFF)) & ((sb + 4) * 8 + s_len * 8 <= nbits_real))
        )
        src_byte = (sb + 4)[:, None] + (j - out_base[:, None])
        s_mask = stored[:, None] & (j >= out_base[:, None]) & (
            j < (out_base + s_len)[:, None]
        )
        s_vals = jnp.take_along_axis(
            bytes_pad, jnp.clip(src_byte, 0, C + 7), axis=1
        )
        lit_plane = jnp.where(s_mask, True, lit_plane)
        val_plane = jnp.where(s_mask, s_vals, val_plane)

        # ---- dynamic header parse (btype=10) ---------------------------
        at = bitpos + 3
        hlit = (window(at[:, None])[:, 0] & 31).astype(jnp.int32) + 257
        hdist = (window((at + 5)[:, None])[:, 0] & 31).astype(jnp.int32) + 1
        hclen = (window((at + 10)[:, None])[:, 0] & 15).astype(jnp.int32) + 4
        is_dyn = live & (btype == 2)
        ok = ok & (~is_dyn | ((hlit <= 286) & (hdist <= 30)))
        # 19 code-length-code lengths at fixed 3-bit slots, CLC order.
        ci = jnp.arange(19, dtype=jnp.int32)[None, :]
        cl_raw = (
            window(at[:, None] + 14 + 3 * ci) & 7
        ).astype(jnp.int32)
        cl_raw = jnp.where(ci < hclen[:, None], cl_raw, 0)
        cl_lens = jnp.zeros((B, 19), jnp.int32).at[
            jnp.arange(B)[:, None], clc_order[None, :]
        ].set(cl_raw)
        cl_tables = _canonical_decoder(cl_lens, 7)
        ok = ok & (~is_dyn | _kraft_valid(cl_tables[1], 7, allow_single=False))
        total_codes = hlit + hdist

        def hstep(carry, _):
            pos, cnt, prev, okh = carry
            w = window(pos[:, None])[:, 0]
            r7 = rev8[(w & 0x7F).astype(jnp.int32)] >> 1
            csym, cL, cmatch = _canon_decode(r7, cl_tables, 7)
            ext = (w >> cL.astype(jnp.uint32)).astype(jnp.int32)
            rep = jnp.where(
                csym < 16,
                1,
                jnp.where(
                    csym == 16,
                    3 + (ext & 3),
                    jnp.where(csym == 17, 3 + (ext & 7), 11 + (ext & 127)),
                ),
            )
            val = jnp.where(
                csym < 16, csym, jnp.where(csym == 16, prev, 0)
            )
            nb = cL + jnp.where(
                csym < 16,
                0,
                jnp.where(csym == 16, 2, jnp.where(csym == 17, 3, 7)),
            )
            act = cnt < total_codes
            okh = okh & (~act | cmatch)
            return (
                pos + jnp.where(act, nb, 0),
                cnt + jnp.where(act, rep, 0),
                jnp.where(act, val, prev),
                okh,
            ), (jnp.where(act, rep, 0), val)

        (hpos, hcnt, _, hok), (reps, vals) = jax.lax.scan(
            hstep,
            (at + 14 + 3 * hclen, jnp.zeros((B,), jnp.int32),
             jnp.zeros((B,), jnp.int32), jnp.ones((B,), bool)),
            None,
            length=_MAX_HDR_TOKENS,
        )
        ok = ok & (~is_dyn | (hok & (hcnt == total_codes)))
        reps_t = reps.T  # [B, 318]
        vals_t = vals.T
        cum_rep = jnp.cumsum(reps_t, axis=1)
        m = jnp.arange(_MAX_HDR_TOKENS, dtype=jnp.int32)[None, :]
        tok_of_m = jax.vmap(partial(jnp.searchsorted, side="right"))(
            cum_rep, jnp.broadcast_to(m, (B, _MAX_HDR_TOKENS))
        ).astype(jnp.int32)
        lens_all = jnp.take_along_axis(
            vals_t, jnp.clip(tok_of_m, 0, _MAX_HDR_TOKENS - 1), axis=1
        )
        li288 = jnp.arange(288, dtype=jnp.int32)[None, :]
        dyn_ll = jnp.where(
            li288 < hlit[:, None],
            jnp.take_along_axis(
                lens_all, jnp.minimum(li288, _MAX_HDR_TOKENS - 1), axis=1
            ),
            0,
        )
        di32 = jnp.arange(32, dtype=jnp.int32)[None, :]
        dyn_dl = jnp.where(
            di32 < hdist[:, None],
            jnp.take_along_axis(
                lens_all,
                jnp.clip(hlit[:, None] + di32, 0, _MAX_HDR_TOKENS - 1),
                axis=1,
            ),
            0,
        )

        use_dyn = (btype == 2)[:, None]
        ll_lens = jnp.where(use_dyn, dyn_ll, fixed_ll[None, :])
        dl_lens = jnp.where(use_dyn, dyn_dl, fixed_dl[None, :])
        ll_tables = _canonical_decoder(ll_lens, 15)
        dl_tables = _canonical_decoder(dl_lens, 15)
        # For dynamic members ll_lens == dyn_ll (and likewise dist), so the
        # decoder's own histograms serve; non-dynamic members are masked.
        ok = ok & (
            ~is_dyn
            | (
                _kraft_valid(ll_tables[1], 15)
                & _kraft_valid(dl_tables[1], 15)
            )
        )
        data_start = jnp.where(btype == 2, hpos, bitpos + 3)

        # ---- speculative token resolve at every bit position -----------
        w = window(p)
        sym, L, matched = _canon_decode(rev15(w), ll_tables, 15)
        islit = matched & (sym < 256)
        iseob = matched & (sym == 256)
        islen = matched & (sym > 256) & (sym < 286)
        bad = ~matched | (matched & (sym >= 286))
        li = jnp.clip(sym - 257, 0, 28)
        lext = len_extra[li]
        lenval = len_base[li] + (
            (w >> L.astype(jnp.uint32)).astype(jnp.int32) & ((1 << lext) - 1)
        )
        pd = p + L + lext
        wd = window(pd)
        dsym, Ld, dmatch = _canon_decode(rev15(wd), dl_tables, 15)
        bad = bad | (islen & (~dmatch | (dsym >= 30)))
        dsym = jnp.clip(dsym, 0, 29)
        dext = dist_extra[dsym]
        dist = dist_base[dsym] + (
            (wd >> Ld.astype(jnp.uint32)).astype(jnp.int32)
            & ((1 << dext) - 1)
        )
        adv = jnp.where(islit | iseob, L, L + lext + Ld + dext)
        nxt = jnp.where(iseob, p, jnp.minimum(p + adv, NB - 1))
        emit = jnp.where(islit, 1, jnp.where(islen, lenval, 0))
        overrun = (~iseob) & ((p + adv) > nbits_real[:, None])
        bad = bad | overrun
        emit = jnp.where(bad, 0, emit)

        # ---- chain walk from the block's first data bit ----------------
        cur = _chain_walk(nxt, data_start, T)

        huff = live & (btype == 1) | live & (btype == 2)
        bad_t = jnp.take_along_axis(bad, cur, axis=1)
        term_t = jnp.take_along_axis(iseob, cur, axis=1)
        reached = term_t[:, -1]
        ok = ok & (~huff | (~jnp.any(bad_t, axis=1) & reached))
        emit_t = jnp.take_along_axis(emit, cur, axis=1)
        emit_t = jnp.where(huff[:, None], emit_t, 0)
        cum_out = jnp.cumsum(emit_t, axis=1)
        tok_off = out_base[:, None] + cum_out - emit_t
        total = jnp.where(huff, cum_out[:, -1], 0)

        # ---- merge this block's coverage into the member planes --------
        jj = j - out_base[:, None]
        cov = _coverage(cum_out, jnp.clip(jj, 0, OUT), T)
        tp = jnp.take_along_axis(cur, cov, axis=1)
        in_blk = huff[:, None] & (jj >= 0) & (jj < total[:, None])
        lit_j = jnp.take_along_axis(islit, tp, axis=1)
        sym_j = jnp.take_along_axis(sym, tp, axis=1).astype(jnp.uint8)
        d_j = jnp.maximum(jnp.take_along_axis(dist, tp, axis=1), 1)
        o_j = jnp.take_along_axis(tok_off, cov, axis=1)
        lit_plane = jnp.where(in_blk, lit_j, lit_plane)
        val_plane = jnp.where(in_blk & lit_j, sym_j, val_plane)
        dst_plane = jnp.where(in_blk, d_j, dst_plane)
        off_plane = jnp.where(in_blk, o_j, off_plane)

        # ---- advance cursor / bookkeeping ------------------------------
        eob_pos = cur[:, -1]
        eob_L = jnp.take_along_axis(L, eob_pos[:, None], axis=1)[:, 0]
        nxt_bit = jnp.where(
            btype == 0,
            (sb + 4) * 8 + s_len * 8,
            eob_pos + eob_L,
        )
        out_base = out_base + jnp.where(
            live, jnp.where(stored, s_len, total), 0
        )
        done = done | (live & bfinal)
        bitpos = jnp.where(live, nxt_bit, bitpos)
        return (bitpos, out_base, ok, done,
                lit_plane, val_plane, dst_plane, off_plane)

    # Early-exit outer loop: stop as soon as every member is done (or
    # failed) instead of paying max_blocks full passes — typical zlib
    # members hold 1-4 blocks, so this is the common 2-4x saving (and the
    # graph holds ONE block body, not max_blocks unrolled copies).
    def _cond(state):
        blk, carry = state
        ok_c, done_c = carry[2], carry[3]
        return (blk < max_blocks) & jnp.any(ok_c & ~done_c)

    def _body(state):
        blk, carry = state
        return blk + 1, _block_step(carry)

    _, (bitpos, out_base, ok, done,
        lit_plane, val_plane, dst_plane, off_plane) = jax.lax.while_loop(
        _cond,
        _body,
        (
            jnp.int32(0),
            (bitpos, out_base, ok, done,
             lit_plane, val_plane, dst_plane, off_plane),
        ),
    )

    ok = ok & done & (out_base == isizes) & (isizes <= OUT)

    # ---- member-wide LZ77 copy resolution (spans blocks, shared) -------
    covered = j < out_base[:, None]
    out, neg = _lz77_resolve(
        lit_plane, val_plane, dst_plane, off_plane, covered, j
    )
    ok = ok & ~neg
    return out, ok


# --------------------------------------------------------------------------
# Host wrappers: full BGZF streams ↔ device codec, with framing + CRC here.
# --------------------------------------------------------------------------


class CodecTierStats:
    """Per-call tier accounting for the device codec wrappers.

    ``bgzf_decompress_device`` / ``bgzf_compress_device`` populate a fresh
    instance per call (module globals ``LAST_INFLATE_STATS`` /
    ``LAST_DEFLATE_STATS``) and mirror every field into METRICS counters
    (``flate.inflate.*`` / ``flate.deflate.*``), which the CLI's
    ``--metrics`` JSON report surfaces next to the sort/markdup spans.

    Fields: members taken per tier (``lanes`` / ``xla`` / ``host``) and
    tier-down causes out of the lanes tier (``tierdown_size`` — member
    shape past the streaming caps, ``tierdown_vmem`` — launch geometry
    past the VMEM budget, ``tierdown_ok0`` — the kernel itself declined,
    i.e. corrupt data or an in-kernel budget overflow).
    """

    __slots__ = (
        "lanes", "xla", "host",
        "tierdown_size", "tierdown_vmem", "tierdown_ok0",
    )

    def __init__(self) -> None:
        self.lanes = 0
        self.xla = 0
        self.host = 0
        self.tierdown_size = 0
        self.tierdown_vmem = 0
        self.tierdown_ok0 = 0

    @property
    def total(self) -> int:
        return self.lanes + self.xla + self.host

    def lanes_hit_rate(self) -> float:
        """Fraction of members the lanes tier actually took (1.0 = no
        tier-downs) — the bench artifact's ``device_*_tier_hit_rate``."""
        t = self.total
        return self.lanes / t if t else 0.0

    def as_dict(self) -> dict:
        return {k: getattr(self, k) for k in self.__slots__}

    def publish(self, prefix: str) -> None:
        from ..utils.tracing import METRICS, current_request

        for k in self.__slots__:
            v = getattr(self, k)
            if v:
                METRICS.count(f"{prefix}.{k}", v)
        rctx = current_request()
        if rctx is not None and self.total:
            # The per-call tier verdict as a request hop: which codec
            # tier actually served this request's members (the serve
            # waterfall's "which kernel ran" answer).  Named ``codec.*``
            # — NOT ``tier.*``, which the tail sampler treats as a
            # degradation trigger; a clean all-lanes call is not one.
            rctx.annotate(
                f"codec.{prefix.rsplit('.', 1)[-1]}",
                **{
                    k: getattr(self, k)
                    for k in self.__slots__
                    if getattr(self, k)
                },
            )


#: Tier accounting of the most recent wrapper call (read by bench.py).
LAST_INFLATE_STATS = CodecTierStats()
LAST_DEFLATE_STATS = CodecTierStats()


def inflate_lanes_accepts(max_clen: int, max_isize: int) -> Tuple[bool, str]:
    """Pure-host tier selection for the streaming lanes decoder: would a
    member of this compressed/inflated shape ride the lanes tier?  Returns
    ``(True, "")`` or ``(False, "size"|"vmem")``.  A full 64 KiB BGZF
    member is accepted — the point of the HBM-streaming geometry."""
    from .pallas.inflate_lanes import accepts

    return accepts(max_clen, max_isize)


def deflate_lanes_accepts(max_plen: int) -> Tuple[bool, str]:
    """Pure-host tier selection for the streaming lanes encoder (mirror of
    :func:`inflate_lanes_accepts`); payloads up to the part writer's
    ``DEV_MAX_PAYLOAD`` blocking are accepted."""
    from .pallas.deflate_lanes import accepts

    return accepts(max_plen)


def _tier_enabled(env_var: str, conf_key: str, conf=None) -> bool:
    """The platform rule every device codec tier shares: the ``env_var``
    force (0/1) → the ``conf_key`` conf key → on exactly when the JAX
    backend is a TPU (``utils.backend.on_tpu``, which initializes the
    backend itself).  Off a TPU the kernels would only run in interpret
    mode, so the host codecs win."""
    env = os.environ.get(env_var)
    if env is not None:
        return env.strip().lower() not in ("0", "false", "no", "off", "")
    if conf is not None and conf_key in conf:
        return conf.get_boolean(conf_key)
    from ..utils.backend import on_tpu

    return on_tpu()


def lanes_tier_enabled(conf=None) -> bool:
    """Should BGZF inflate route through the lockstep-lane Pallas tier?
    ``HBAM_INFLATE_LANES`` → ``hadoopbam.inflate.lanes`` → the platform
    rule (:func:`_tier_enabled`)."""
    from ..conf import INFLATE_LANES

    return _tier_enabled("HBAM_INFLATE_LANES", INFLATE_LANES, conf)


def deflate_lanes_tier_enabled(conf=None) -> bool:
    """Should BGZF deflate route through the lockstep-lane LZ77 encoder?
    ``HBAM_DEFLATE_LANES`` → ``hadoopbam.deflate.lanes`` → the platform
    rule."""
    from ..conf import DEFLATE_LANES

    return _tier_enabled("HBAM_DEFLATE_LANES", DEFLATE_LANES, conf)


def rans_lanes_tier_enabled(conf=None) -> bool:
    """Should CRAM rANS 4x8 decode route through the lockstep-lane tier
    (ops/pallas/rans_lanes.py)?  ``HBAM_RANS_LANES`` →
    ``hadoopbam.cram.rans-lanes`` → the platform rule.  Slices the device
    tier declines or flags tier down per-slice to the NumPy host
    decoder."""
    from ..conf import CRAM_RANS_LANES

    return _tier_enabled("HBAM_RANS_LANES", CRAM_RANS_LANES, conf)


def bcf_chain_tier_enabled(conf=None) -> bool:
    """Should BCF record-chain walks route through the device kernel
    (ops/pallas/bcf_chain.py)?  ``HBAM_BCF_CHAIN`` →
    ``hadoopbam.bcf.chain`` → the platform rule.  Windows the device walk
    declines tier down per-window to the bit-exact NumPy walk."""
    from ..conf import BCF_CHAIN

    return _tier_enabled("HBAM_BCF_CHAIN", BCF_CHAIN, conf)


def device_write_enabled(conf=None) -> bool:
    """Should part writes assemble on device (sorted gather + markdup
    flag patch + CRC32 over the HBM-resident split payloads, feeding the
    deflate lanes device-to-device)?  ``HBAM_DEVICE_WRITE`` →
    ``hadoopbam.write.device`` → the platform rule.  Per part the path
    still tiers down to the host gather when the batch lacks residency
    or the geometry leaves the device domain (reasons in
    ``bam.device_write_tierdown.*``)."""
    from ..conf import WRITE_DEVICE

    return _tier_enabled("HBAM_DEVICE_WRITE", WRITE_DEVICE, conf)


def _lanes_decode_members(
    raw: np.ndarray, co, cs, xlen, idx: List[int], us,
    stats: Optional[CodecTierStats] = None,
    keep_device: bool = False,
) -> Tuple[dict, int, Optional[object]]:
    """Run the lockstep-lane decoder over the members in ``idx``.

    Returns ``({member_index: payload_bytes}, n_tierdown, dev)`` — members
    the lanes tier could not decode are simply absent and flow to the next
    tier.  A launch that exhausts device memory counts every member as a
    tier-down (visible in METRICS); any other launch error propagates.  Members
    whose shape the streaming geometry rejects are filtered host-side
    (``inflate_lanes_accepts``) so one oversized member no longer tiers
    down its whole launch; ``stats`` (when given) records the tier-down
    taxonomy.  With ``keep_device`` the per-lane device byte view rides
    back for the on-chip output-residency handoff (None unless every
    member decoded clean)."""
    from ..utils.tracing import METRICS
    from .pallas.inflate_lanes import inflate_lanes_ex

    with span("flate.inflate.pack"):
        clens_all = np.asarray(
            [cs[i] - 20 - xlen[i] for i in idx], dtype=np.int32
        )
        isz_all = np.asarray([us[i] for i in idx], dtype=np.int32)
        take: List[int] = []
        for k in range(len(idx)):
            ok_k, reason = inflate_lanes_accepts(
                int(clens_all[k]), int(isz_all[k])
            )
            if ok_k:
                take.append(k)
            elif stats is not None:
                if reason == "size":
                    stats.tierdown_size += 1
                else:
                    stats.tierdown_vmem += 1
        if not take:
            if len(idx):
                METRICS.count("flate.lanes_tierdown", len(idx))
            return {}, len(idx), None
        clens = clens_all[take]
        isz = isz_all[take]
        comp = np.zeros((len(take), max(int(clens.max()), 1)), np.uint8)
        for k2, k in enumerate(take):
            i = idx[k]
            s = int(co[i]) + 12 + int(xlen[i])
            comp[k2, : clens[k2]] = raw[s : s + clens[k2]]
    from ..utils.tracing import count_d2h, count_h2d

    count_h2d(comp.nbytes, "inflate_comp")
    try:
        out_l, ok_l, dev = inflate_lanes_ex(
            comp, clens, isz, keep_device=keep_device
        )
    except Exception as e:
        from ..utils.backend import is_resource_exhausted

        # Device memory exhausted is the one launch failure that tiers
        # down (the serve OOM ladder's declared path); a compile or
        # lowering error is a bug and propagates.
        if not is_resource_exhausted(e):
            raise
        METRICS.count("flate.lanes_launch_error", 1)
        METRICS.count("flate.oom_tierdown", 1)
        from ..utils.tracing import current_request

        rctx = current_request()
        if rctx is not None:
            # A codec tier decision is a request hop: a served request
            # whose members tiered down names the seam in its waterfall
            # instead of just paying an unexplained slower decode.
            rctx.annotate(
                "tier.inflate_lanes_down", members=len(idx), oom=True
            )
        if stats is not None:
            stats.tierdown_ok0 += len(idx)
        return {}, len(idx), None
    with span("flate.inflate.verify"):
        decoded = {
            idx[take[k2]]: out_l[k2, : isz[k2]].tobytes()
            for k2 in range(len(take))
            if ok_l[k2]
        }
    count_d2h(int(sum(len(v) for v in decoded.values())), "inflate_out")
    if stats is not None:
        stats.tierdown_ok0 += int((~ok_l).sum())
    n_down = len(idx) - len(decoded)
    if n_down:
        METRICS.count("flate.lanes_tierdown", n_down)
        dev = None  # the device view is only exact when everything decoded
    if dev is not None and len(take) != len(idx):
        dev = None
    return decoded, n_down, dev


@partial(jax.jit, static_argnums=(2,))
def _device_flatten(rows, starts, n_total: int):
    """Concatenate ragged per-member payload prefixes into one
    device-resident byte stream: member m's row lands at ``starts[m]``
    (increasing), whole, and the next member overwrites the garbage past
    its payload.  Row copies, not a per-byte gather: the stream is a
    split's whole inflated window (hundreds of MB)."""
    width = rows.shape[1]

    def put(m, out):
        return jax.lax.dynamic_update_slice(out, rows[m], (starts[m],))

    out = jnp.zeros(n_total + width, jnp.uint8)
    out = jax.lax.fori_loop(0, rows.shape[0], put, out)
    return out[:n_total]


@_trace_stage("flate.stage.inflate_device")
def inflate_blocks_device(
    data,
    coffsets: np.ndarray,
    csizes: np.ndarray,
    usizes: np.ndarray,
    check_crc: bool = True,
    return_device: bool = False,
):
    """Device-tier drop-in for :func:`native.inflate_blocks`.

    Same contract — ``(out, out_offsets)`` with block i's payload at
    ``out[out_offsets[i]:out_offsets[i+1]]`` — but the member payloads
    ship to the accelerator *compressed* (≈4x fewer h2d bytes than the
    inflated stream) and inflate on the lockstep-lane tier; members the
    tier rejects fall back to native host zlib per member.  This is the
    split-read surface: ``io.bam.read_virtual_range(device_inflate=True)``
    routes its batched block inflate here when the lanes tier is enabled.

    ``return_device`` adds a third return value: a device-resident uint8
    array holding the same concatenated payload stream (the on-chip
    output-residency handoff — the device-parse chain kernel can consume
    it without the d2h→h2d bounce), or ``None`` whenever the device copy
    would not be byte-exact (any tier-down, CRC retry or host-replayed
    far copy).
    """
    from .. import native

    raw = np.frombuffer(data, dtype=np.uint8) if not isinstance(
        data, np.ndarray
    ) else data
    n = len(coffsets)
    out_offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.asarray(usizes, dtype=np.int64), out=out_offsets[1:])
    out = np.empty(int(out_offsets[-1]), dtype=np.uint8)
    co64 = np.asarray(coffsets, dtype=np.int64)
    xlen = raw[co64 + 10].astype(np.int32) | (
        raw[co64 + 11].astype(np.int32) << 8
    )
    live = [i for i in range(n) if usizes[i] > 0]
    decoded, _, dev2d = (
        _lanes_decode_members(
            raw, coffsets, csizes, xlen, live, usizes,
            keep_device=return_device,
        )
        if live
        else ({}, 0, None)
    )
    fallback: List[int] = []
    with span("flate.inflate.verify"):
        for i in live:
            payload = decoded.get(i)
            if payload is None:
                fallback.append(i)
                continue
            if check_crc:
                crc = struct.unpack_from(
                    "<I", raw, int(coffsets[i]) + int(csizes[i]) - 8
                )[0]
                if (zlib.crc32(payload) & 0xFFFFFFFF) != crc:
                    # The host re-decode decides corrupt-vs-bug.
                    fallback.append(i)
                    continue
            out[out_offsets[i] : out_offsets[i + 1]] = np.frombuffer(
                payload, dtype=np.uint8
            )
        if fallback:
            dev2d = None  # host bytes diverge from the device copy
            f_out, f_offs = native.inflate_blocks(
                raw,
                co64[fallback],
                np.asarray(csizes, dtype=np.int32)[fallback],
                np.asarray(usizes, dtype=np.int32)[fallback],
                check_crc=check_crc,
            )
            for k, i in enumerate(fallback):
                out[out_offsets[i] : out_offsets[i + 1]] = f_out[
                    f_offs[k] : f_offs[k + 1]
                ]
    if not return_device:
        return out, out_offsets
    dev_flat = None
    if dev2d is not None and len(out):
        # Rows of the device view are the live members in order; empty
        # members contribute zero bytes and need no row.
        starts = out_offsets[:-1][np.asarray(usizes) > 0].astype(np.int32)
        from ..utils.hbm import LEDGER
        from ..utils.tracing import METRICS

        with span("flate.inflate.flatten"):
            dev_flat = _device_flatten(
                dev2d, jnp.asarray(starts), int(out_offsets[-1])
            )
        # Residency ledger: the inflate tier now owns a split window in
        # HBM; the read path transfers ownership when it attaches the
        # window to a RecordBatch, and whoever holds it last must
        # release it — an unreleased window is a named leak.
        dev_flat = LEDGER.register(
            dev_flat, kind="split_window", holder="flate.inflate_device",
            nbytes=int(out_offsets[-1]),
        )
        METRICS.count("flate.inflate_device_residency", 1)
    return out, out_offsets, dev_flat


def _pow2_at_least(n: int, lo: int) -> int:
    v = lo
    while v < n:
        v *= 2
    return v


def _deflate_fixed_rows(
    mat: np.ndarray, lens: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Literal-only fixed-Huffman emit over padded member rows (the XLA
    :func:`deflate_fixed` kernel, launch-chunked).  Returns (comp rows,
    clens)."""
    nblk, P = mat.shape
    out_bytes = (3 + 9 * P + 7 + 7) // 8 + 1
    step = max(1, _MAX_LAUNCH_ELEMS // (out_bytes * 8))
    comp_rows: List[np.ndarray] = []
    clen_rows: List[np.ndarray] = []
    for g0 in range(0, nblk, step):
        c, cl = deflate_fixed(
            jnp.asarray(mat[g0 : g0 + step]),
            jnp.asarray(lens[g0 : g0 + step]),
            out_bytes,
        )
        comp_rows.append(np.asarray(c))
        clen_rows.append(np.asarray(cl))
    return np.concatenate(comp_rows), np.concatenate(clen_rows)


def _host_raw_deflate(payload: np.ndarray, level: int) -> bytes:
    """One member's payload through host zlib as a raw DEFLATE stream —
    the per-member tier-down target when the lanes encoder declines."""
    co = zlib.compressobj(max(1, min(level, 9)), zlib.DEFLATED, -15)
    return co.compress(payload.tobytes()) + co.flush()


def bgzf_compress_device(
    data,
    block_payload: Optional[int] = None,
    append_terminator: bool = True,
    level: int = 1,
    conf=None,
    use_lanes: Optional[bool] = None,
    device_input=None,
    donate_input: bool = False,
) -> bytes:
    """Compress a byte stream into BGZF using the device deflate tiers.

    Framing (gzip headers, CRC32, ISIZE) is host-side; the DEFLATE emit
    runs on device for all blocks at once.  Tiers, top to bottom:

    1. ``level == 0``: stored members (one final stored block per
       member) — no device work, bit-faithful to "uncompressed parts".
    2. **Lockstep-lane LZ77 encoder** (ops/pallas/deflate_lanes.py), when
       ``use_lanes`` is True or the :func:`deflate_lanes_tier_enabled`
       gate fires: real match-finding compression; members the kernel
       declines (geometry past the VMEM budget) tier down per member to
       host zlib at ``level``.
    3. **Literal-only fixed-Huffman** (:func:`deflate_fixed`): the
       original XLA emit — valid DEFLATE, ratio traded for zero host CPU
       and zero serial device work.

    ``block_payload`` defaults per tier (``DEV_LZ_PAYLOAD`` — full-size
    streaming members — for the lanes encoder, ``DEV_DEFAULT_PAYLOAD``
    otherwise); per-block CRC32 runs over slices of the original
    contiguous input, and the stream is assembled in one preallocated
    buffer.

    ``device_input`` (a device-resident uint8 array, exclusive with
    ``data``) is the device-resident write path's handoff: the lanes
    encoder reads its member windows straight from HBM
    (``deflate_lanes_stream``) and the per-member CRC32 runs on chip
    (``ops.pallas.crc32``), so the uncompressed stream never visits the
    host — only the compressed rows, the 4-byte CRC column and any
    tier-down members' payloads come back d2h (ledgered under
    ``transfers.d2h.*``).  Output is byte-identical to the host-input
    path on the same bytes.

    ``donate_input`` marks the caller done with ``device_input`` after
    this call: the on-chip CRC launch — the stream's *final* reader in
    this function's ordering (deflate rows → per-member tier-downs →
    CRC) — donates the buffer (the CRC kernel's ``donate=True``), so on
    donation-capable backends the gathered part stream's HBM is
    reusable the moment the CRC dispatches instead of surviving until
    the caller's release.  This is the DeviceStream's gather→deflate
    donation seam; backends without donation run identically minus the
    aliasing.

    Per-call tier accounting lands in :data:`LAST_DEFLATE_STATS` (and the
    ``flate.deflate.*`` METRICS counters): members per tier plus the
    size/vmem/ok0 tier-down taxonomy out of the lanes tier."""
    global LAST_DEFLATE_STATS
    from ..utils.tracing import METRICS, count_d2h

    stats = CodecTierStats()
    LAST_DEFLATE_STATS = stats
    a: Optional[np.ndarray]
    if device_input is not None:
        if data is not None:
            raise ValueError("pass data or device_input, not both")
        a = None
        n = int(device_input.shape[0])
    else:
        a = np.frombuffer(data, dtype=np.uint8) if not isinstance(
            data, np.ndarray
        ) else data
        n = len(a)
    if use_lanes is None:
        use_lanes = level != 0 and deflate_lanes_tier_enabled(conf)
    if device_input is not None and (level == 0 or not use_lanes):
        # Device-resident input only pays off on the lanes tier; the
        # stored/XLA tiers need the bytes host-side anyway — spill once,
        # visibly, and continue exactly as the host-input path.
        a = np.asarray(device_input)
        count_d2h(a.nbytes, "write_spill")
        METRICS.count("flate.deflate.device_input_spill", 1)
        device_input = None
    if block_payload is None:
        block_payload = DEV_LZ_PAYLOAD if use_lanes else DEV_DEFAULT_PAYLOAD
    if block_payload > DEV_MAX_PAYLOAD:
        raise bgzf.BgzfError(
            f"device codec payload cap is {DEV_MAX_PAYLOAD}, "
            f"got {block_payload}"
        )
    nblk = max(1, -(-n // block_payload))
    lens = np.full(nblk, block_payload, dtype=np.int32)
    if n:
        lens[-1] = n - (nblk - 1) * block_payload
    else:
        lens[0] = 0

    comp: Optional[np.ndarray] = None  # padded rows (device tiers)
    clens = np.zeros(nblk, dtype=np.int64)
    overrides: dict = {}  # member index -> bytes (stored / host tiers)

    def _member_payload(i: int) -> np.ndarray:
        """Member i's raw payload, host-side — the per-member tier-down
        target.  On the device-input path this is the only payload d2h,
        and only for members the lanes tier declined."""
        s = i * block_payload
        ln = int(lens[i])
        if a is not None:
            return a[s : s + ln]
        sl = np.asarray(device_input[s : s + ln])
        count_d2h(sl.nbytes, "write_tierdown")
        return sl

    if level == 0:
        # Uncompressed parts: one final stored block per member (LEN/NLEN
        # framing only; an empty member is the 5-byte empty stored block).
        for i in range(nblk):
            s = i * block_payload
            ln = int(lens[i])
            overrides[i] = (
                b"\x01"
                + struct.pack("<HH", ln, ln ^ 0xFFFF)
                + a[s : s + ln].tobytes()
            )
            clens[i] = 5 + ln
        stats.host += nblk
    else:
        mat: Optional[np.ndarray] = None
        if a is not None:
            P = max(int(lens.max()), 1)
            mat = np.zeros((nblk, P), dtype=np.uint8)
            for i in range(nblk):
                s = i * block_payload
                mat[i, : lens[i]] = a[s : s + lens[i]]
        done = False
        if use_lanes:
            from .pallas.deflate_lanes import (
                deflate_lanes,
                deflate_lanes_stream,
            )

            accepted, reason = deflate_lanes_accepts(int(lens.max()))
            if not accepted:
                if reason == "size":
                    stats.tierdown_size += nblk
                else:
                    stats.tierdown_vmem += nblk
                ok = np.zeros(nblk, dtype=bool)
            else:
                try:
                    if device_input is not None:
                        # HBM-resident payload: member windows are the
                        # deterministic blocking cuts, read on device.
                        comp, cl, ok = deflate_lanes_stream(
                            device_input, lens
                        )
                    else:
                        comp, cl, ok = deflate_lanes(mat, lens)
                except Exception as e:
                    from ..utils.backend import is_resource_exhausted

                    # Only HBM exhaustion tiers down; a compile or
                    # lowering error propagates.
                    if not is_resource_exhausted(e):
                        raise
                    METRICS.count("flate.deflate_lanes_launch_error", 1)
                    ok = np.zeros(nblk, dtype=bool)
                stats.tierdown_ok0 += int((~ok).sum())
            stats.lanes += int(ok.sum())
            if ok.any():
                clens[:] = cl
                done = True
            n_down = int((~ok).sum())
            if n_down:
                METRICS.count("flate.deflate_lanes_tierdown", n_down)
                stats.host += n_down
                for i in np.nonzero(~ok)[0]:
                    overrides[int(i)] = _host_raw_deflate(
                        _member_payload(int(i)), level
                    )
                    clens[int(i)] = len(overrides[int(i)])
                done = True
        if not done:
            if mat is None:
                # The lanes tier never engaged and the XLA emit needs the
                # payload rows host-side: spill the device input.
                a = np.asarray(device_input)
                count_d2h(a.nbytes, "write_spill")
                METRICS.count("flate.deflate.device_input_spill", 1)
                device_input = None
                P = max(int(lens.max()), 1)
                mat = np.zeros((nblk, P), dtype=np.uint8)
                for i in range(nblk):
                    s = i * block_payload
                    mat[i, : lens[i]] = a[s : s + lens[i]]
            comp, cl = _deflate_fixed_rows(mat, lens)
            clens[:] = cl
            stats.xla += nblk
    if faults.ACTIVE is not None and level != 0:
        # Forced tier-down seam: selected members drop to host zlib no
        # matter which device tier produced them — the cascade must stay
        # bit-exact through the framing below (tests/test_faults.py).
        for i in range(nblk):
            if faults.ACTIVE.flate_tierdown("deflate", i):
                overrides[i] = _host_raw_deflate(_member_payload(i), level)
                clens[i] = len(overrides[i])
    stats.publish("flate.deflate")

    # ---- framing: one preallocated pass, CRC over the input itself -----
    # Host input: zlib.crc32 over slices of the contiguous stream.
    # Device input: the on-chip slice-by-4 kernel over the HBM-resident
    # stream — the framing never touches the uncompressed bytes, only a
    # 4-byte CRC column comes back d2h.
    dev_crcs: Optional[np.ndarray] = None
    if a is None:
        from ..utils.hbm import LEDGER
        from .pallas.crc32 import crc32_device

        with span("flate.deflate.crc"):
            crc_dev = crc32_device(
                device_input,
                np.arange(nblk, dtype=np.int64) * block_payload,
                lens.astype(np.int64),
                donate=donate_input,
            )
        if donate_input:
            from ..utils.backend import donation_supported

            if donation_supported():
                METRICS.count("flate.deflate.input_donated", 1)
        # The on-chip CRC column is ledgered for its (short) residency:
        # registered, fetched, released — device bytes accounted even
        # when the lifetime is one statement.
        LEDGER.register(
            crc_dev, kind="crc_column", holder="flate.deflate_crc"
        )
        with span("flate.deflate.wait"):
            dev_crcs = np.asarray(crc_dev)
        LEDGER.release(crc_dev)
        count_d2h(dev_crcs.nbytes, "write_crc")
    with span("flate.deflate.frame"):
        total = int((18 + 8) * nblk + clens.sum())
        if append_terminator:
            total += len(bgzf.TERMINATOR)
        buf = bytearray(total)
        pos = 0
        off_in = 0
        for i in range(nblk):
            c = int(clens[i])
            ln = int(lens[i])
            bsize = c + 12 + 6 + 8
            buf[pos : pos + 4] = bgzf.MAGIC
            struct.pack_into(
                "<IBBHBBHH", buf, pos + 4, 0, 0, 0xFF, 6, 0x42, 0x43, 2,
                bsize - 1,
            )
            pos += 18
            od = overrides.get(i)
            if od is not None:
                buf[pos : pos + c] = od
            else:
                buf[pos : pos + c] = memoryview(comp[i, :c])
            pos += c
            crc = (
                int(dev_crcs[i])
                if dev_crcs is not None
                else zlib.crc32(a[off_in : off_in + ln]) & 0xFFFFFFFF
            )
            struct.pack_into("<II", buf, pos, crc, ln)
            pos += 8
            off_in += ln
        if append_terminator:
            buf[pos:] = bgzf.TERMINATOR
        return bytes(buf)


@_trace_stage("flate.stage.deflate_device")
def deflate_blocks_device(
    payload,
    level: int = 1,
    block_payload: Optional[int] = None,
    conf=None,
    use_lanes: Optional[bool] = None,
    device_input=None,
    donate_input: bool = False,
) -> bytes:
    """Device-tier drop-in for :func:`native.deflate_blocks` (no
    terminator appended): the part-write surface of the lockstep-lane
    encoder.  With host ``payload`` the caller gathers the sorted records
    and the LZ77 match-find + Huffman emit run on chip; with
    ``device_input`` (the device-resident write path) the gathered stream
    is already in HBM and the lanes encoder + CRC32 both read it there —
    the host does framing over compressed rows and a 4-byte CRC column
    only.  Blocking is deterministic (payload cut every ``block_payload``
    bytes), so ``write_part_fast``'s analytic splitting-bai voffset math
    holds with the same ``block_payload``."""
    return bgzf_compress_device(
        payload,
        block_payload=block_payload,
        append_terminator=False,
        level=level,
        conf=conf,
        use_lanes=use_lanes,
        device_input=device_input,
        donate_input=donate_input,
    )


def bgzf_decompress_device(
    data,
    check_crc: bool = True,
    _force_no_host: bool = False,
    conf=None,
) -> bytes:
    """Decompress a whole BGZF stream, batching members onto the device.

    When the lockstep-lane tier is enabled (``hadoopbam.inflate.lanes`` /
    ``HBAM_INFLATE_LANES`` / the platform rule — see
    :func:`lanes_tier_enabled`), every member first rides the general
    Pallas decoder (ops/pallas/inflate_lanes.py); only members it rejects
    continue below.  The remainder are grouped by first-block DEFLATE
    flavor and dispatched to the matching XLA kernel —
    ``inflate_stored`` / ``inflate_fixed`` / ``inflate_dynamic`` (the
    general decoder; real zlib output at level ≥1 is dynamic-Huffman and
    decodes on device).  A member whose specialized kernel rejects it
    (mixed block flavors) retries through the general decoder, and only a
    member the device cannot decode at all tiers down to native host
    zlib — same data, same result, tiered like the split planner
    (BAMInputFormat.java:244-258).  The chain is lanes → XLA → host and
    correctness never depends on a device tier.  ``_force_no_host`` turns
    the last tier into an error (device-only mode, used by tests).

    Per-call tier accounting lands in :data:`LAST_INFLATE_STATS` (and the
    ``flate.inflate.*`` METRICS counters): members per tier plus the
    size/vmem/ok0 tier-down taxonomy out of the lanes tier."""
    global LAST_INFLATE_STATS
    from .. import native

    stats = CodecTierStats()
    LAST_INFLATE_STATS = stats

    raw = np.frombuffer(data, dtype=np.uint8) if not isinstance(
        data, np.ndarray
    ) else data
    co, cs, us = native.scan_blocks(raw)
    nblk = len(co)
    outs: List[Optional[bytes]] = [None] * nblk
    # Per-member XLEN (u16 at header offset 10): BGZF requires the BC
    # subfield but permits additional extra subfields, so the DEFLATE
    # payload starts at co+12+xlen, not a hardcoded co+18 (ADVICE r1).
    co64 = np.asarray(co, dtype=np.int64)
    xlen = raw[co64 + 10].astype(np.int32) | (
        raw[co64 + 11].astype(np.int32) << 8
    )
    groups: dict = {"stored": [], "fixed": [], "dyn": []}
    for i in range(nblk):
        # Empty member (e.g. the 28-byte EOF terminator): an empty DEFLATE
        # payload is ≤2 bytes, so cs ≤ 22+xlen — short-circuit, no kernel.
        if us[i] == 0 and cs[i] <= 22 + xlen[i]:
            outs[i] = b""
            continue
        first = int(raw[int(co[i]) + 12 + int(xlen[i])])  # first payload byte
        hdr3 = first & 7
        if hdr3 in (0, 1):  # stored, possibly a non-final chain (zlib lvl 0)
            groups["stored"].append(i)
        elif hdr3 in (2, 3):
            groups["fixed"].append(i)
        else:
            # Dynamic-Huffman first block (zlib level ≥1, i.e. essentially
            # every real-world BAM): the device decoder builds the
            # canonical tables per member/block on chip.
            groups["dyn"].append(i)
    if faults.ACTIVE is not None:
        # Forced tier-down seam: fired members skip every device tier and
        # host-decode immediately (corrupt data still raises, exactly as
        # a real per-member tier-down would surface it).
        forced = [
            i
            for kind in groups
            for i in groups[kind]
            if faults.ACTIVE.flate_tierdown("inflate", i)
        ]
        for i in forced:
            member = raw[int(co[i]) : int(co[i]) + int(cs[i])]
            outs[i], _ = bgzf.inflate_block(member.tobytes(), 0, check_crc)
            stats.host += 1
        if forced:
            fset = set(forced)
            for kind in groups:
                groups[kind] = [i for i in groups[kind] if i not in fset]
    # ---- Tier 1: the general lockstep-lane Pallas decoder --------------
    # One pass over every member regardless of block flavor (the lanes
    # kernel walks any stored/fixed/dynamic mix); members it rejects stay
    # in their flavor group and continue through the XLA tiers below.
    lanes_idx = (
        groups["stored"] + groups["fixed"] + groups["dyn"]
        if lanes_tier_enabled(conf)
        else []
    )
    if lanes_idx:
        decoded, _, _ = _lanes_decode_members(
            raw, co, cs, xlen, lanes_idx, us, stats=stats
        )
        stats.lanes += len(decoded)
        for i, payload in decoded.items():
            outs[i] = payload
        for kind in groups:
            groups[kind] = [i for i in groups[kind] if i not in decoded]
    for kind in ("stored", "fixed", "dyn"):
        idx = groups[kind]
        if not idx:
            continue
        # Payload = member bytes between the (12+xlen)-byte header and
        # 8-byte footer; bucket the compressed width to bound recompiles.
        clens = np.asarray(
            [cs[i] - 20 - xlen[i] for i in idx], dtype=np.int32
        )
        isz = np.asarray([us[i] for i in idx], dtype=np.int32)
        C = _pow2_at_least(int(clens.max()), 512)
        OUT = _pow2_at_least(int(isz.max()) if len(isz) else 1, 1024)
        fn = {
            "stored": inflate_stored,
            "fixed": inflate_fixed,
            "dyn": inflate_dynamic,
        }[kind]
        # Cap the members per kernel launch: bounded HBM footprint AND the
        # TPU gather-index precision limit, on BOTH the bit-position
        # (C*8) and output-byte (OUT) gather extents.
        widest = max(C * 8 if kind != "stored" else C, OUT)
        step = max(1, _MAX_LAUNCH_ELEMS // widest)
        for g0 in range(0, len(idx), step):
            gi = idx[g0 : g0 + step]
            gc = clens[g0 : g0 + step]
            gz = isz[g0 : g0 + step]
            comp = np.zeros((len(gi), C), dtype=np.uint8)
            for k, i in enumerate(gi):
                s = int(co[i]) + 12 + int(xlen[i])
                comp[k, : gc[k]] = raw[s : s + gc[k]]
            from ..utils.tracing import count_d2h, count_h2d

            count_h2d(comp.nbytes, "inflate_comp")
            if kind == "fixed" and jax.default_backend() == "tpu":
                # Preferred tier on real chips: the lockstep-lane Pallas
                # decoder for literal-only fixed members (everything the
                # device deflate emits).  Members outside its contract
                # come back ok=False and fall through to the XLA kernels
                # below.  Never taken on CPU: interpret-mode emulation of
                # the lockstep walk is far slower than the XLA path.
                from ..utils.tracing import METRICS
                from .pallas.inflate_fixed import inflate_fixed_literal

                try:
                    out_l, ok_l = inflate_fixed_literal(comp, gc, gz)
                except Exception as e:
                    from ..utils.backend import is_resource_exhausted

                    # HBM exhaustion tiers down, counted; a compile or
                    # lowering error propagates.
                    if not is_resource_exhausted(e):
                        raise
                    METRICS.count("flate.lockstep_launch_error", 1)
                    ok_l = np.zeros(len(gi), dtype=bool)
                    out_l = None
                all_ok = bool(ok_l.all()) if len(ok_l) else False
                for k, i in enumerate(gi):
                    if ok_l[k]:
                        outs[i] = out_l[k, : gz[k]].tobytes()
                        stats.lanes += 1
                if all_ok:
                    continue
                METRICS.count(
                    "flate.lockstep_tierdown", int((~ok_l).sum())
                )
            if kind == "fixed":
                # pow2-bucketed like C so distinct jit signatures stay few.
                cbits = _pow2_at_least(int(gc.max()) * 8, 4096)
                out_d, ok = fn(
                    jnp.asarray(comp),
                    jnp.asarray(gc),
                    jnp.asarray(gz),
                    OUT,
                    cbits,
                )
            else:
                out_d, ok = fn(
                    jnp.asarray(comp), jnp.asarray(gc), jnp.asarray(gz), OUT
                )
            out_d = np.asarray(out_d)
            ok = np.asarray(ok)
            count_d2h(out_d.nbytes, "inflate_out")
            for k, i in enumerate(gi):
                if outs[i] is not None:
                    # Already decoded by the lockstep Pallas tier in a
                    # mixed fixed group — keep that result.
                    continue
                if ok[k]:
                    outs[i] = out_d[k, : gz[k]].tobytes()
                    stats.xla += 1
                elif kind != "dyn":
                    # Routing by the first block's btype is best-effort:
                    # zlib may mix block flavors inside one member (e.g. a
                    # fixed or stored first block followed by dynamic
                    # ones).  The general decoder handles any mix — retry
                    # there, still on device.
                    groups["dyn"].append(i)
                elif _force_no_host:
                    raise bgzf.BgzfError(
                        f"device inflate failed for member at offset {co[i]}"
                    )
                else:
                    # Device tiers down to the host codec for just this
                    # member (raises if the data itself is corrupt).
                    member = raw[int(co[i]) : int(co[i]) + int(cs[i])]
                    payload, _ = bgzf.inflate_block(
                        member.tobytes(), 0, check_crc
                    )
                    outs[i] = payload
                    stats.host += 1
    stats.publish("flate.inflate")
    if check_crc:
        for i in range(nblk):
            if us[i] == 0:
                continue
            crc = struct.unpack_from(
                "<I", raw, int(co[i]) + int(cs[i]) - 8
            )[0]
            if (zlib.crc32(outs[i]) & 0xFFFFFFFF) != crc:
                if _force_no_host:
                    raise bgzf.BgzfError(
                        f"CRC mismatch in BGZF member at offset {co[i]}"
                    )
                # Device result failed the host CRC gate: re-decode this
                # member on the host tier (raises BgzfError if the data —
                # not the device — is what's corrupt).
                member = raw[int(co[i]) : int(co[i]) + int(cs[i])]
                outs[i], _ = bgzf.inflate_block(
                    member.tobytes(), 0, check_crc=True
                )
    return b"".join(outs)  # type: ignore[arg-type]
