"""The plain reference of a coordinate sort: what ``sort`` must write.

Hadoop-BAM's sort key (``BAMRecordReader.getKey``): a mapped read keys on
``refID << 32 | pos`` as a Java ``long`` (the int ``pos`` sign-extends); a
read with the unmapped flag, a negative refID or an alignment start below 0
keys on ``INT_MAX << 32 | h``, where ``h`` is the low 32 bits, sign-extended,
of Hadoop-BAM's MurmurHash3 over the record's bytes after its 32-byte fixed
part.  Records of equal key keep their input order.  The output header is
the input's with ``@HD SO:coordinate``, every record is copied byte for
byte, and the records are deflated at the traffic mix's ``output_level``.
Everything here is numpy and ``zlib``.

The numbers compared for one output file (:func:`compare`):
``framing_errors``, ``header_wrong``, ``records_wrong``, and
``output_size_excess``: how much larger the record members are than zlib
makes the same records at the output level, as a share of zlib's size.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np

from benchmark import bgzf

INT_MAX = 2**31 - 1
_M64 = np.uint64(0xFFFFFFFFFFFFFFFF)
_C1 = np.uint64(0x87C37B91114253D5)
_C2 = np.uint64(0x4CF5AD432745937F)


def _rotl(x, r: int):
    return (x << np.uint64(r)) | (x >> np.uint64(64 - r))


def _fmix(k):
    k = k ^ (k >> np.uint64(33))
    k = k * np.uint64(0xFF51AFD7ED558CCD)
    k = k ^ (k >> np.uint64(33))
    k = k * np.uint64(0xC4CEB9FE1A85EC53)
    return k ^ (k >> np.uint64(33))


def murmur3_low32(data: np.ndarray, offs: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Hadoop-BAM's ``MurmurHash3.murmurhash3(bytes, 0)`` (x64_128's ``h1``,
    with its one departure: ``h2`` rotates in ``h1 >>> 33``), low 32 bits
    as int32, for every slice ``data[offs[i]:offs[i]+lens[i]]``."""
    n = len(offs)
    if n == 0:
        return np.zeros(0, np.int32)
    offs = np.asarray(offs, np.int64)
    lens = np.asarray(lens, np.int64)
    h1 = np.zeros(n, np.uint64)
    h2 = np.zeros(n, np.uint64)
    blocks = lens // 16
    last = len(data) - 1

    def word(at):  # little-endian u64 at byte offsets ``at`` (clamped)
        b = data[np.minimum(at[:, None] + np.arange(8)[None, :], last)].astype(np.uint64)
        return (b << (np.uint64(8) * np.arange(8, dtype=np.uint64))[None, :]).sum(axis=1, dtype=np.uint64)

    with np.errstate(over="ignore"):
        for i in range(int(blocks.max())):
            act = np.flatnonzero(i < blocks)
            at = offs[act] + 16 * i
            k1 = _rotl(word(at) * _C1, 31) * _C2
            k2 = _rotl(word(at + 8) * _C2, 33) * _C1
            a1 = _rotl(h1[act] ^ k1, 27) + h2[act]
            a1 = a1 * np.uint64(5) + np.uint64(0x52DCE729)
            b2 = h2[act] ^ k2
            b2 = ((b2 << np.uint64(31)) | (a1 >> np.uint64(33))) + a1
            h2[act] = b2 * np.uint64(5) + np.uint64(0x38495AB5)
            h1[act] = a1
        rem = lens & 15
        tail_at = offs + 16 * blocks
        t1 = word(tail_at)
        t2 = word(tail_at + 8)
        # Keep only the tail's own bytes.
        n1 = np.minimum(rem, 8).astype(np.uint64)
        n2 = np.clip(rem - 8, 0, 8).astype(np.uint64)
        m1 = np.where(n1 == 8, _M64, (np.uint64(1) << (np.uint64(8) * n1)) - np.uint64(1))
        m2 = np.where(n2 == 8, _M64, (np.uint64(1) << (np.uint64(8) * n2)) - np.uint64(1))
        t1, t2 = t1 & m1, t2 & m2
        h2 = np.where(rem > 8, h2 ^ (_rotl(t2 * _C2, 33) * _C1), h2)
        h1 = np.where(rem > 0, h1 ^ (_rotl(t1 * _C1, 31) * _C2), h1)
        ul = lens.astype(np.uint64)
        h1, h2 = h1 ^ ul, h2 ^ ul
        h1 = h1 + h2
        h2 = h2 + h1
        h1, h2 = _fmix(h1), _fmix(h2)
        h1 = h1 + h2
    return (h1 & np.uint64(0xFFFFFFFF)).astype(np.uint32).view(np.int32)


def _i32(stream: np.ndarray, at: np.ndarray) -> np.ndarray:
    b = stream[at[:, None] + np.arange(4)[None, :]].astype(np.uint32)
    return (b[:, 0] | (b[:, 1] << 8) | (b[:, 2] << 16) | (b[:, 3] << 24)).view(np.int32)


def check_chain(stream: np.ndarray, offsets: np.ndarray, lengths: np.ndarray) -> None:
    """Every record's block_size word agrees with the record table."""
    if len(offsets) and (
        not np.array_equal(_i32(stream, offsets).astype(np.int64) + 4, lengths)
        or int(offsets[-1] + lengths[-1]) != len(stream)
        or not np.array_equal(offsets[1:], offsets[:-1] + lengths[:-1])
    ):
        raise ValueError("record table does not match the stream's block_size chain")


def sort_keys(stream: np.ndarray, offsets: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    refid = _i32(stream, offsets + 4).astype(np.int64)
    pos = _i32(stream, offsets + 8).astype(np.int64)
    flag = stream[offsets + 18].astype(np.int64) | (stream[offsets + 19].astype(np.int64) << 8)
    unmapped = ((flag & 4) != 0) | (refid < 0) | (pos + 1 < 0)
    keys = (refid << 32) | pos  # numpy int64 OR sign-extends pos like Java
    um = np.flatnonzero(unmapped)
    h = murmur3_low32(stream, offsets[um] + 36, lengths[um] - 36).astype(np.int64)
    keys[um] = (np.int64(INT_MAX) << 32) | h
    return keys


def sorted_header_text(text: str) -> str:
    """``@HD SO:coordinate`` (any ``GO:`` dropped); an ``@HD`` line is added
    where there is none."""
    lines = text.split("\n")
    for i, line in enumerate(lines):
        if line.startswith("@HD"):
            fields = [f for f in line.split("\t") if not f.startswith(("SO:", "GO:"))]
            lines[i] = "\t".join(fields + ["SO:coordinate"])
            return "\n".join(lines)
    return "@HD\tVN:1.6\tSO:coordinate\n" + text


@dataclass
class Expected:
    header: bytes
    records: bytes  # every record, in sorted order
    lengths: np.ndarray  # per record, in sorted order
    level: int  # the output's zlib level
    level_bytes: int  # deflate payload of the record members zlib writes at that level

    def records_array(self):
        """The records as ``(stream, offsets, lengths)``."""
        offsets = np.zeros(len(self.lengths), np.int64)
        np.cumsum(self.lengths[:-1], out=offsets[1:])
        return np.frombuffer(self.records, np.uint8), offsets, self.lengths

    def to_bam(self, level: int) -> bytes:
        """This answer as a BAM file at zlib ``level``."""
        return bgzf.bam_bytes(self.header, *self.records_array(), level)


def expected(corpus, traffic: dict, order: np.ndarray = None) -> Expected:
    """What the sort must write for ``corpus`` (``order`` replaces the
    sorted order, for controls)."""
    check_chain(corpus.stream, corpus.offsets, corpus.lengths)
    if order is None:
        order = np.argsort(sort_keys(corpus.stream, corpus.offsets, corpus.lengths), kind="stable")
    mv = memoryview(corpus.stream)
    offs = corpus.offsets[order].tolist()
    ends = (corpus.offsets[order] + corpus.lengths[order]).tolist()
    records = b"".join([mv[a:b] for a, b in zip(offs, ends)])
    lengths = corpus.lengths[order]
    level = int(traffic["output_level"])
    want = Expected(
        header=bgzf.header_block(sorted_header_text(corpus.header_text), corpus.refs),
        records=records, lengths=lengths, level=level, level_bytes=0,
    )
    want.level_bytes = bgzf.record_deflated_bytes(*want.records_array(), level)
    return want


def work(want: Expected) -> Dict[str, float]:
    """The work a job needs, for rooflines: the record bytes it writes, and
    their deflated size at the output level as zlib makes it (not as the
    program made it, so that writing more bytes reads as no gain)."""
    return {"output_inflated": len(want.records), "output_compressed": want.level_bytes}


def ties_reversed(corpus, want: Expected, traffic: dict) -> bytes:
    """Control: records of equal key in reverse input order."""
    keys = sort_keys(corpus.stream, corpus.offsets, corpus.lengths)
    order = np.lexsort((-np.arange(len(keys)), keys))
    return expected(corpus, traffic, order).to_bam(want.level)


def stored(corpus, want: Expected, traffic: dict) -> bytes:
    """Control: the right records, written as stored members (level 0)."""
    return want.to_bam(0)


#: Answers that break one guarantee each; ``correct`` must come out false
#: for every one of them.
CONTROLS = {"ties_reversed": ties_reversed, "stored": stored}


def _header_len(payload: bytes) -> int:
    if payload[:4] != b"BAM\x01":
        return -1
    lt = int.from_bytes(payload[4:8], "little", signed=True)
    p = 8 + lt
    nref = int.from_bytes(payload[p : p + 4], "little", signed=True)
    p += 4
    for _ in range(max(nref, 0)):
        ln = int.from_bytes(payload[p : p + 4], "little", signed=True)
        p += 8 + ln
    return p if p <= len(payload) else -1


def _split_records(body: bytes) -> List[bytes]:
    out, p = [], 0
    while p + 4 <= len(body):
        n = int.from_bytes(body[p : p + 4], "little", signed=True) + 4
        if n < 36 or p + n > len(body):
            out.append(body[p:])
            break
        out.append(body[p : p + n])
        p += n
    return out


def compare(output: bytes, want: Expected) -> Dict[str, float]:
    """The numbers compared for one output file: BGZF framing faults, a
    header that differs, record slots that differ from the reference's (a
    missing or extra record counts once), and the record members' size
    over zlib's at the output level, less one."""
    read = bgzf.read_bgzf(output)
    payload = read.payload
    hlen = _header_len(payload)
    header_wrong = int(hlen < 0 or payload[:hlen] != want.header)
    body = payload[hlen:] if hlen >= 0 else b""
    if body == want.records:
        wrong = 0
    else:
        got = _split_records(body)
        offs = np.concatenate([[0], np.cumsum(want.lengths)]).tolist()
        exp = [want.records[offs[i] : offs[i + 1]] for i in range(len(want.lengths))]
        wrong = sum(a != b for a, b in zip(got, exp)) + abs(len(got) - len(exp))
    size = bgzf.record_member_bytes(read.members_sizes, len(want.header))
    return {
        "framing_errors": read.framing_errors,
        "header_wrong": header_wrong,
        "records_wrong": wrong,
        "output_size_excess": size / max(want.level_bytes, 1) - 1.0,
    }
