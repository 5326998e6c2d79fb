"""BGZF, written and read with nothing but ``zlib`` (SAM spec section 4.1).

The writer blocks records the way htslib's ``bam_write1`` does: the header
in members of its own, then records packed into members of at most
``MAX_PAYLOAD`` bytes without splitting a record.  The reader checks every
member's framing (magic, flags, the ``BC`` subfield, ``BSIZE``, CRC32,
``ISIZE``) and the end-of-file member, and counts each fault it finds.
"""

from __future__ import annotations

import os
import struct
import zlib
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

#: htslib's ``BGZF_BLOCK_SIZE``: the most payload one member carries.
MAX_PAYLOAD = 0xFF00
EOF_MEMBER = bytes.fromhex(
    "1f8b08040000000000ff0600424302001b0003000000000000000000"
)
_HEAD = struct.Struct("<4BIBBHBBHH")  # ID1 ID2 CM FLG MTIME XFL OS XLEN SI1 SI2 SLEN BSIZE


def _threads() -> int:
    return max(1, min(16, os.cpu_count() or 1))


def member(payload: bytes, level: int) -> bytes:
    co = zlib.compressobj(level, zlib.DEFLATED, -15)
    cdata = co.compress(payload) + co.flush()
    head = _HEAD.pack(31, 139, 8, 4, 0, 0, 255, 6, 66, 67, 2, 25 + len(cdata))
    return head + cdata + struct.pack("<II", zlib.crc32(payload), len(payload))


def header_block(text: str, refs) -> bytes:
    """The BAM header: magic, text, reference dictionary."""
    t = text.encode()
    out = [b"BAM\x01", struct.pack("<i", len(t)), t, struct.pack("<i", len(refs))]
    for name, length in refs:
        nb = name.encode() + b"\x00"
        out += [struct.pack("<i", len(nb)), nb, struct.pack("<i", length)]
    return b"".join(out)


def record_blocks(offsets: np.ndarray, lengths: np.ndarray) -> List[Tuple[int, int]]:
    """``[start, end)`` byte ranges of the stream, whole records each, at
    most ``MAX_PAYLOAD`` bytes."""
    ends = offsets + lengths
    out = []
    i, n = 0, len(offsets)
    while i < n:
        start = int(offsets[i])
        j = int(np.searchsorted(ends, start + MAX_PAYLOAD, side="right"))
        j = max(j, i + 1)
        out.append((start, int(ends[j - 1])))
        i = j
    return out


@dataclass
class Written:
    compressed_bytes: int  # deflate payload of every member
    inflated_bytes: int
    members: int
    record_compressed: int  # the same, of the members that hold records
    record_inflated: int


def members(header: bytes, stream: np.ndarray, offsets, lengths, level: int) -> Tuple[List[bytes], int]:
    """The members of a BAM file at zlib ``level``, without the EOF member,
    and how many of them hold the header."""
    pieces = [header[i : i + MAX_PAYLOAD] for i in range(0, len(header), MAX_PAYLOAD)]
    n_head = len(pieces)
    mv = memoryview(stream)
    pieces += [mv[a:b] for a, b in record_blocks(offsets, lengths)]
    with ThreadPoolExecutor(_threads()) as pool:
        return list(pool.map(lambda p: member(bytes(p), level), pieces, chunksize=64)), n_head


def _isize(m: bytes) -> int:
    return int.from_bytes(m[-4:], "little")


def write_bam(path: str, header: bytes, stream: np.ndarray, offsets, lengths, level: int) -> Written:
    """Writes a BAM file; returns what its members hold."""
    ms, n_head = members(header, stream, offsets, lengths, level)
    with open(path, "wb") as f:
        f.writelines(ms)
        f.write(EOF_MEMBER)
    return Written(
        compressed_bytes=sum(len(m) - 26 for m in ms),
        inflated_bytes=sum(_isize(m) for m in ms),
        members=len(ms),
        record_compressed=sum(len(m) - 26 for m in ms[n_head:]),
        record_inflated=sum(_isize(m) for m in ms[n_head:]),
    )


def bam_bytes(header: bytes, stream: np.ndarray, offsets, lengths, level: int) -> bytes:
    """A whole BAM file, EOF member included, at zlib ``level``."""
    return b"".join(members(header, stream, offsets, lengths, level)[0]) + EOF_MEMBER


def record_deflated_bytes(stream: np.ndarray, offsets, lengths, level: int) -> int:
    """Deflate payload bytes of the record members zlib writes at
    ``level``."""
    return sum(len(m) - 26 for m in members(b"", stream, offsets, lengths, level)[0])


@dataclass
class Read:
    payload: bytes
    framing_errors: int
    members_sizes: List[Tuple[int, int]]  # (deflated, inflated) per member


def record_member_bytes(sizes: List[Tuple[int, int]], header_len: int) -> int:
    """Deflated bytes of the members past the ones that hold only the
    header."""
    seen, out = 0, 0
    for c, i in sizes:
        if seen + i > header_len:
            out += c
        seen += i
    return out


def _inflate(data: memoryview, off: int) -> Tuple[bytes, str]:
    try:
        (id1, id2, cm, flg, _mt, _xfl, _os, xlen, si1, si2, slen, bsize) = _HEAD.unpack_from(data, off)
    except struct.error:
        return b"", f"truncated member header at {off}"
    if (id1, id2, cm, flg, xlen, si1, si2, slen) != (31, 139, 8, 4, 6, 66, 67, 2):
        return b"", f"bad member header at {off}"
    end = off + bsize + 1
    if end > len(data):
        return b"", f"member at {off} runs past the end"
    try:
        d = zlib.decompressobj(-15)
        payload = d.decompress(data[off + 18 : end - 8])
        if not d.eof or d.unused_data:
            return b"", f"deflate stream of member at {off} does not end at its BSIZE"
    except zlib.error as e:
        return b"", f"member at {off}: {e}"
    crc, isize = struct.unpack_from("<II", data, end - 8)
    if crc != zlib.crc32(payload) or isize != len(payload):
        return b"", f"CRC32 or ISIZE of member at {off} does not match"
    return payload, ""


def read_bgzf(data: bytes) -> Read:
    """Inflates a whole BGZF file, checking each member and the EOF
    member."""
    mv = memoryview(data)
    offs = []
    off = 0
    errors = 0
    while off < len(data):
        if off + 18 > len(data):  # trailing bytes
            errors += 1
            break
        bsize = struct.unpack_from("<H", data, off + 16)[0]
        offs.append(off)
        off += bsize + 1
    if not offs or offs[-1] != len(data) - len(EOF_MEMBER) or not data.endswith(EOF_MEMBER):
        errors += 1
    else:
        offs.pop()  # the EOF member, checked whole above
    with ThreadPoolExecutor(_threads()) as pool:
        results = list(pool.map(lambda o: _inflate(mv, o), offs, chunksize=256))
    errors += sum(1 for _, err in results if err)
    sizes = [
        (struct.unpack_from("<H", data, o + 16)[0] + 1 - 26, len(p)) for o, (p, _) in zip(offs, results)
    ]
    return Read(b"".join(p for p, _ in results), errors, sizes)
