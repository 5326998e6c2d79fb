"""Lockstep-lane Pallas inflate for literal-only fixed-Huffman members.

The first production slice of the lockstep-lane decoder design measured
by ops/pallas/inflate_probe.py: up to 128 BGZF members ride the 128
vector lanes of one kernel, each walking its own DEFLATE bit stream
serially — per-lane bit cursors, window extraction as dense iota-compare
column reductions over the transposed [words, 128] stream tile, fixed-
table decode as pure elementwise arithmetic.

Scope: single-block btype=01 members whose symbols are literals + EOB —
exactly what the device deflate (ops/flate.py deflate_fixed) emits, so
device-compressed BGZF round-trips entirely through Pallas.  The
restriction buys the key structural win: every token emits exactly ONE
byte, so the output row equals the wave index and all 128 lanes store
through one aligned full-row write every 4 waves — no scatter anywhere.
A member using length/distance codes (symbols 257+) or a non-01 block
header flags itself invalid and tiers down to the general XLA decoder
(ops/flate.py), same stance as every other fallback in the codec.

Oracle: zlib via spec/bgzf.py; tests run the kernel in interpret mode on
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

LANES = 128


def _kernel_factory(R: int, T: int):
    """R stream words per lane; T output bytes capacity (waves)."""

    def kernel(streams_ref, nbits_ref, out_ref, count_ref, ok_ref):
        rows = lax.broadcasted_iota(jnp.int32, (R, LANES), 0)

        def word_at(widx):
            onehot = rows == widx  # [R,128]
            return jnp.sum(
                jnp.where(onehot, streams_ref[:, :], 0),
                axis=0,
                keepdims=True,
            ).astype(jnp.uint32)

        def window(cur):
            widx = cur >> 5
            w0 = word_at(widx)
            w1 = word_at(widx + 1)
            sh = (cur & 31).astype(jnp.uint32)
            return jnp.where(
                sh == 0, w0, (w0 >> sh) | (w1 << (32 - sh))
            )

        nbits = nbits_ref[:, :]
        # Block header: bfinal=1, btype=01 → low 3 bits 0b011.
        hdr = window(jnp.zeros((1, LANES), jnp.int32))
        ok = (hdr & 7) == 3
        cur = jnp.full((1, LANES), 3, jnp.int32)
        done = ~ok  # invalid members stop immediately

        def body(t, state):
            cur, done, ok, word_acc, count = state
            w = window(cur)
            # Fixed-Huffman decode: reverse the next 9 stream bits
            # (codes are MSB-first), then classify by canonical ranges.
            rev = jnp.zeros((1, LANES), jnp.uint32)
            for k in range(9):
                rev = rev | (((w >> k) & 1) << (8 - k))
            c7 = (rev >> 2).astype(jnp.int32)
            c8 = (rev >> 1).astype(jnp.int32)
            c9 = rev.astype(jnp.int32)
            is7 = c7 <= 0x17          # symbols 256-279 (len 7)
            is_eob = c7 == 0
            is8 = (~is7) & (c8 >= 0x30) & (c8 <= 0xBF)  # literals 0-143
            # 280-287 are EXACTLY 0xC0-0xC7: the 9-bit literals
            # (0x190-0x1FF) share the 0xC8+ 8-bit prefixes.
            is8_len = (~is7) & (c8 >= 0xC0) & (c8 <= 0xC7)
            is9 = (~is7) & (~is8) & (~is8_len)          # literals 144-255
            lit = jnp.where(
                is8, c8 - 0x30, jnp.where(is9, c9 - 0x190 + 144, 0)
            )
            # Literal-only contract: a non-EOB 7-bit symbol (257-279) or
            # an 8-bit length symbol means LZ77 — tier down.
            bad = (is7 & ~is_eob) | is8_len
            adv = jnp.where(is7, 7, jnp.where(is8, 8, 9))
            live = ~done
            ok = ok & (~live | ~bad)
            emits = live & ~bad & ~is_eob
            # All emitting lanes write output byte t: pack into a word
            # register, flush the full row every 4th wave (aligned).
            byte = jnp.where(emits, lit, 0).astype(jnp.uint32)
            word_acc = word_acc | (byte << (8 * (t & 3)))
            @pl.when((t & 3) == 3)
            def _():
                out_ref[pl.ds(t >> 2, 1), :] = word_acc.astype(jnp.int32)
            word_acc = jnp.where((t & 3) == 3, 0, word_acc)
            count = count + emits.astype(jnp.int32)
            done_now = live & (bad | is_eob)
            # The EOB must end inside the member's real bit length.
            ok = ok & (
                ~done_now | (cur + adv <= nbits)
            )
            done = done | done_now
            cur = jnp.where(live & ~bad & ~is_eob, cur + adv, cur)
            # Consume the EOB itself so the final cursor check holds.
            cur = jnp.where(live & is_eob, cur + 7, cur)
            return cur, done, ok, word_acc, count

        word_acc0 = jnp.zeros((1, LANES), jnp.uint32)
        count0 = jnp.zeros((1, LANES), jnp.int32)
        cur, done, ok, word_acc, count = lax.fori_loop(
            0, T, body, (cur, done, ok, word_acc0, count0)
        )
        # Flush the trailing partial word.  Row T>>2: the partial row when
        # T%4 != 0, else the spare row past the last full flush (writing
        # (T-1)>>2 would zero a row the loop already flushed).
        out_ref[pl.ds(T >> 2, 1), :] = word_acc.astype(jnp.int32)
        ok = ok & done  # never reached EOB within T waves → invalid
        count_ref[:, :] = count
        ok_ref[:, :] = ok.astype(jnp.int32)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("r_words", "t_waves", "interpret")
)
def _inflate_fixed_kernel(streams, nbits, r_words: int, t_waves: int, interpret: bool):
    kernel = _kernel_factory(r_words, t_waves)
    out_rows = -(-t_waves // 4) + 1
    return pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((out_rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((1, LANES), jnp.int32),
            jax.ShapeDtypeStruct((1, LANES), jnp.int32),
        ),
        interpret=interpret,
    )(streams, nbits)


#: VMEM budget for one launch (streams + output tiles + headroom).  The
#: whole member rides VMEM in this slice, so members past the budget
#: come back ok=False and tier down to the XLA decoder; a windowed
#: HBM-streaming variant is the follow-up that lifts the cap.
_VMEM_BUDGET_BYTES = 10 << 20


def inflate_fixed_literal(
    comp: np.ndarray,
    clens: np.ndarray,
    isizes: np.ndarray,
    interpret=None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Batched lockstep inflate of literal-only fixed-Huffman members.

    ``comp`` uint8 [B, C] (rows zero-padded), ``clens``/``isizes`` int32
    [B].  Returns ``(out uint8 [B, max_isize], ok bool [B])`` — a member
    that violates the literal-only/single-block contract, exceeds the
    VMEM budget, or whose output disagrees in length comes back
    ``ok=False`` and the caller tiers down to the general decoder.
    """
    from ..flate import _pow2_at_least

    B, C = comp.shape
    if B == 0:
        return np.empty((0, 0), np.uint8), np.empty(0, bool)
    max_out = int(isizes.max()) if len(isizes) else 0
    t_waves = _pow2_at_least(max_out + 4, 64)
    r_words = _pow2_at_least(-(-C // 4) + 2, 64)
    vmem = (r_words + t_waves // 4 + 1) * LANES * 4
    if vmem > _VMEM_BUDGET_BYTES:
        return (
            np.zeros((B, max_out), np.uint8),
            np.zeros(B, dtype=bool),
        )
    out = np.empty((B, max_out), dtype=np.uint8)
    ok_all = np.empty(B, dtype=bool)
    interpret = pallas_interpret(interpret)
    for g0 in range(0, B, LANES):
        g1 = min(B, g0 + LANES)
        n = g1 - g0
        # Transpose the group: member j's words go down lane j.
        grp = np.zeros((r_words * 4, LANES), dtype=np.uint8)
        grp[:C, :n] = comp[g0:g1].T
        words = (
            grp.reshape(r_words, 4, LANES)
            .astype(np.uint32)
            * (np.uint32(1) << (8 * np.arange(4, dtype=np.uint32)))[
                None, :, None
            ]
        ).sum(axis=1).astype(np.uint32).view(np.int32)
        nbits = np.zeros((1, LANES), dtype=np.int32)
        nbits[0, :n] = clens[g0:g1] * 8
        o, cnt, okk = _inflate_fixed_kernel(
            jnp.asarray(words), jnp.asarray(nbits), r_words, t_waves,
            bool(interpret),
        )
        o = np.asarray(o)
        cnt = np.asarray(cnt)[0]
        okk = np.asarray(okk)[0].astype(bool)
        # Un-transpose: lane j's packed words → member j's bytes.
        by = o.view(np.uint32).astype(np.uint32)
        bytes_mat = np.empty((t_waves, LANES), dtype=np.uint8)
        rows = by[: -(-t_waves // 4) + 1]
        for k in range(4):
            sel = np.arange(k, t_waves, 4)
            bytes_mat[sel] = ((rows[: len(sel)] >> (8 * k)) & 0xFF).astype(
                np.uint8
            )
        for j in range(n):
            i = g0 + j
            okj = okk[j] and int(cnt[j]) == int(isizes[i])
            ok_all[i] = okj
            if okj:
                out[i, : isizes[i]] = bytes_mat[: isizes[i], j]
            else:
                out[i, :] = 0
    return out, ok_all
