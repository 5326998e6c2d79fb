"""Device CRC32 (the gzip/BGZF polynomial) over HBM-resident byte streams.

BGZF member framing needs ``CRC32(payload)`` and ISIZE per member; as long
as that CRC ran on the host, the uncompressed part stream had to exist
host-side even when the gather and the DEFLATE emit were already
device-resident — the whole write path stayed pinned to an h2d upload of
the raw bytes.  This kernel closes the loop: per-member CRCs compute on
chip straight from the HBM-resident gathered stream, so the part writer
d2h's a 4-byte CRC column instead of keeping the payload on the host.

Formulation: slicing-by-4.  The CRC recurrence is serial per *word*, not
per byte — each step folds 4 input bytes through four 256-entry tables:

    c ^= word(LE);  c = T3[c&ff] ^ T2[(c>>8)&ff] ^ T1[(c>>16)&ff] ^ T0[c>>24]

All members of a batch advance in lockstep (one ``fori_loop`` over the
word count of the longest member, retired members carry their value), so
the step is a dense [B]-wide gather program — the shape XLA:TPU runs
well.  A Pallas lockstep variant was considered and rejected: the table
gathers would become O(table)×O(members) one-hot selects per wave (the
probe-style row-select trick), turning a 4-gather step into a 1024-wide
reduction — the XLA gather path is strictly better here, which is why
this member of the kernel family has no ``pallas_call``.

Oracle: ``zlib.crc32`` (tests/test_device_write.py fuzzes empty, 1-byte,
word-boundary and multi-member batches against it bit-for-bit).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax


def _build_tables() -> np.ndarray:
    t = np.zeros((4, 256), dtype=np.uint32)
    for i in range(256):
        c = np.uint32(i)
        for _ in range(8):
            c = (c >> np.uint32(1)) ^ (
                np.uint32(0xEDB88320) if c & np.uint32(1) else np.uint32(0)
            )
        t[0, i] = c
    for k in range(1, 4):
        for i in range(256):
            t[k, i] = (t[k - 1, i] >> np.uint32(8)) ^ t[
                0, int(t[k - 1, i] & np.uint32(0xFF))
            ]
    return t


#: Slicing-by-4 tables for the reflected 0xEDB88320 polynomial; row 0 is
#: the classic bytewise table (used for the ≤3-byte tail).
CRC_TABLES = _build_tables()


def _pow2_at_least(n: int, lo: int) -> int:
    v = lo
    while v < n:
        v *= 2
    return v


def _crc32_core(
    stream: jax.Array, offs: jax.Array, lens: jax.Array, max_words: int
) -> jax.Array:
    """CRC32 of ``stream[offs[i] : offs[i]+lens[i]]`` for every member i,
    in lockstep.  ``max_words`` is the static word-loop bound (≥
    ``max(lens)//4``); members past their own length carry their value.
    Zero-length members return 0 (``zlib.crc32(b"") == 0``)."""
    S = stream.shape[0]
    t0 = jnp.asarray(CRC_TABLES[0])
    t1 = jnp.asarray(CRC_TABLES[1])
    t2 = jnp.asarray(CRC_TABLES[2])
    t3 = jnp.asarray(CRC_TABLES[3])
    offs = offs.astype(jnp.int32)
    lens = lens.astype(jnp.int32)
    nwords = lens >> 2

    def byte_at(idx):
        return stream[jnp.clip(idx, 0, S - 1)].astype(jnp.uint32)

    def word_step(i, crc):
        base = offs + 4 * i
        w = (
            byte_at(base)
            | (byte_at(base + 1) << 8)
            | (byte_at(base + 2) << 16)
            | (byte_at(base + 3) << 24)
        )
        c = crc ^ w
        c2 = (
            t3[(c & 0xFF).astype(jnp.int32)]
            ^ t2[((c >> 8) & 0xFF).astype(jnp.int32)]
            ^ t1[((c >> 16) & 0xFF).astype(jnp.int32)]
            ^ t0[(c >> 24).astype(jnp.int32)]
        )
        return jnp.where(i < nwords, c2, crc)

    crc = jnp.full(offs.shape, 0xFFFFFFFF, dtype=jnp.uint32)
    crc = lax.fori_loop(0, max_words, word_step, crc)
    # Bytewise tail: members whose length is not a word multiple have ≤3
    # trailing bytes (static unroll).
    for k in range(3):
        pos = nwords * 4 + k
        b = byte_at(offs + pos)
        c2 = (crc >> 8) ^ t0[((crc ^ b) & 0xFF).astype(jnp.int32)]
        crc = jnp.where(pos < lens, c2, crc)
    return crc ^ jnp.uint32(0xFFFFFFFF)


_crc32_kernel = functools.partial(jax.jit, static_argnums=(3,))(_crc32_core)
#: The donating twin: the stream argument's buffer is donated to the
#: launch, so the CRC column's allocation may reuse the gathered part
#: stream's HBM — the CRC is the stream's *last* reader on the
#: device-resident write path (``ops.flate.bgzf_compress_device`` orders
#: deflate → tier-downs → CRC), which makes this the gather→deflate
#: seam's buffer-donation point: after the CRC dispatch the part's
#: uncompressed bytes hold no HBM the consumer can't reuse.
_crc32_kernel_donating = functools.partial(
    jax.jit, static_argnums=(3,), donate_argnums=(0,)
)(_crc32_core)


def crc32_device(stream, offs, lens, donate: bool = False) -> jax.Array:
    """Per-member CRC32 over a device-resident byte stream.

    ``stream``: uint8 device array (or anything ``jnp.asarray`` accepts);
    ``offs``/``lens``: int member windows (host numpy — they are O(members)
    and ride up with the launch).  Returns a device uint32 [n_members]
    column; the caller downloads 4 bytes per member, never the payload.

    Launch shapes are pow2-bucketed on both the member count and the word
    loop so distinct jit signatures stay few (the shared-geometry stance
    of the codec kernels).

    ``donate=True`` donates the stream buffer to the launch (the caller
    must be the stream's final reader): requested only when the backend
    supports donation (``utils.backend.donation_supported``), silently a
    plain launch otherwise."""
    offs = np.asarray(offs, dtype=np.int64)
    lens = np.asarray(lens, dtype=np.int64)
    n = len(offs)
    if n == 0:
        return jnp.zeros((0,), jnp.uint32)
    if int(stream.shape[0]) == 0 or int(lens.max()) == 0:
        # Nothing to fold: every member is empty (zlib.crc32(b"") == 0);
        # also sidesteps gathering from a zero-length stream.
        return jnp.zeros((n,), jnp.uint32)
    if int(offs.max()) + int(lens.max()) > 2**31 - 8:
        raise ValueError("crc32_device: stream outside the int32 domain")
    B = _pow2_at_least(n, 8)
    offs_p = np.zeros(B, dtype=np.int32)
    lens_p = np.zeros(B, dtype=np.int32)
    offs_p[:n] = offs
    lens_p[:n] = lens
    max_words = _pow2_at_least(max(int(lens.max()) >> 2, 1), 64)
    if donate:
        from ...utils.backend import donation_supported

        donate = donation_supported()
    kernel = _crc32_kernel_donating if donate else _crc32_kernel
    out = kernel(
        jnp.asarray(stream), jnp.asarray(offs_p), jnp.asarray(lens_p),
        max_words,
    )
    return out[:n]
