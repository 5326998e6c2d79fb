"""Paired-end reads as an aligner writes them before the coordinate sort.

The shape follows ``bwa mem | samtools fixmate -m`` output for Illumina
2x150 bp data: mates adjacent and sharing a name, CIGARs with soft clips
and indels, the aux tags ``NM MD MC AS XS RG MQ ms`` (plus ``XA``/``SA`` on a
few reads), binned NovaSeq qualities, a share of reads unmapped (placed at
the mapped mate, or both mates unplaced), and designed duplicate families.

Every size in the stream is fixed by the configuration's ``layout_seed``:
record lengths, CIGARs, tag sets and the digits of every number.  The run's
``--seed`` draws only the contents: positions, bases, qualities, name
digits, scores.  So every seed gives the sort the same shapes and the same
amount of work, in another order.  The capture design (``design_seed``) is
part of the configuration too.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

from benchmark import bgzf

#: Pairs generated per chunk: bounds the transient index arrays.
CHUNK_PAIRS = 8192

_BASES = np.array([1, 2, 4, 8], dtype=np.uint8)  # A C G T as BAM nibbles
_LETTERS = np.frombuffer(b"ACGT", dtype=np.uint8)


@dataclass
class Corpus:
    """Records back to back, each with its ``block_size`` word."""

    header_text: str
    refs: List[Tuple[str, int]]
    stream: np.ndarray  # uint8
    offsets: np.ndarray  # int64, start of each record's block_size word
    lengths: np.ndarray  # int64, 4 + block_size

    @property
    def n_records(self) -> int:
        return len(self.offsets)


def _rng(*key: int) -> np.random.Generator:
    return np.random.default_rng([k & ((1 << 64) - 1) for k in key])


def _ndigits(v: np.ndarray) -> np.ndarray:
    v = np.asarray(v, dtype=np.int64)
    n = np.ones(v.shape, dtype=np.int64)
    for p in range(1, 19):
        n += v >= 10**p
    return n


def _vdigits(v: np.ndarray, width: int) -> Tuple[np.ndarray, np.ndarray]:
    """Left-aligned ASCII digits of ``v`` (``v < 10**width``) and their
    counts."""
    v = np.asarray(v, dtype=np.int64)
    w = _ndigits(v)
    j = np.arange(width, dtype=np.int64)[None, :]
    p = np.clip(w[:, None] - 1 - j, 0, None)
    d = (v[:, None] // (10**p)) % 10
    return np.where(j < w[:, None], 48 + d, 0).astype(np.uint8), w


def _const(text: bytes, n: int) -> np.ndarray:
    return np.tile(np.frombuffer(text, dtype=np.uint8), (n, 1))


def _le(v: np.ndarray, dtype) -> np.ndarray:
    """Little-endian bytes of ``v`` as an ``(n, itemsize)`` matrix."""
    a = np.ascontiguousarray(np.asarray(v).astype(np.dtype(dtype).newbyteorder("<")))
    return a.view(np.uint8).reshape(len(a), -1)


def reg2bin(beg: np.ndarray, end: np.ndarray) -> np.ndarray:
    """SAM spec ``reg2bin`` over ``[beg, end)``, vectorized."""
    beg = np.asarray(beg, dtype=np.int64)
    e = np.asarray(end, dtype=np.int64) - 1
    out = np.zeros(beg.shape, dtype=np.int64)
    done = np.zeros(beg.shape, dtype=bool)
    for shift, base in ((14, 4681), (17, 585), (20, 73), (23, 9), (26, 1)):
        hit = ~done & ((beg >> shift) == (e >> shift))
        out = np.where(hit, base + (beg >> shift), out)
        done |= hit
    return out


class _Rows:
    """Records assembled from byte components, each an ``(n, W)`` matrix
    with a per-row length (0 leaves the component out of that row)."""

    def __init__(self, n: int) -> None:
        self.n = n
        self.parts: List[Tuple[np.ndarray, np.ndarray]] = []

    def add(self, mat: np.ndarray, lens=None, present=None) -> None:
        mat = np.asarray(mat, dtype=np.uint8).reshape(self.n, -1)
        if lens is None:
            lens = np.full(self.n, mat.shape[1], dtype=np.int64)
        lens = np.asarray(lens, dtype=np.int64)
        if present is not None:
            lens = np.where(present, lens, 0)
        self.parts.append((mat, lens))

    def add_z(self, tag: bytes, pieces, present=None) -> None:
        """A ``Z`` tag made of ``(mat, lens)`` pieces."""
        self.add(_const(tag + b"Z", self.n), present=present)
        for mat, lens in pieces:
            self.add(mat, lens, present)
        self.add(np.zeros((self.n, 1), np.uint8), present=present)

    def assemble(self) -> Tuple[np.ndarray, np.ndarray]:
        """Returns ``(stream, lengths)``; each record is prefixed with its
        block_size word."""
        body = np.zeros(self.n, dtype=np.int64)
        for _, lens in self.parts:
            body += lens
        lengths = body + 4
        starts = np.zeros(self.n, dtype=np.int64)
        np.cumsum(lengths[:-1], out=starts[1:])
        out = np.zeros(int(lengths.sum()), dtype=np.uint8)
        cur = starts.copy()
        self._put(out, cur, _le(body, np.int32), np.full(self.n, 4))
        for mat, lens in self.parts:
            self._put(out, cur, mat, lens)
        return out, lengths

    @staticmethod
    def _put(out, cur, mat, lens) -> None:
        width = mat.shape[1]
        if width == 0:
            return
        col = np.arange(width, dtype=np.int64)[None, :]
        if lens.min() == width:
            out[cur[:, None] + col] = mat
        else:
            keep = col < lens[:, None]
            out[(cur[:, None] + col)[keep]] = mat[keep]
        cur += lens


def _cigar_layout(lay: np.random.Generator, n: int, read_len: int, mix):
    """Per record: op codes and lengths (up to 3 ops) from the layout."""
    cls = lay.choice(5, size=n, p=mix)
    a_clip = lay.integers(10, 50, n)  # soft clip: aS bM, b has 3 digits
    a_ind = lay.integers(60, 100, n)  # indel: aM bX cM, c has 2 digits
    b_ind = lay.integers(1, 10, n)
    ops = np.zeros((n, 3), np.int64)
    lens = np.zeros((n, 3), np.int64)
    n_ops = np.ones(n, np.int64)
    lens[:, 0] = read_len
    m = cls == 1
    ops[m, 0], lens[m, 0], ops[m, 1], lens[m, 1] = 4, a_clip[m], 0, read_len - a_clip[m]
    n_ops[m] = 2
    m = cls == 2
    ops[m, 0], lens[m, 0], ops[m, 1], lens[m, 1] = 0, read_len - a_clip[m], 4, a_clip[m]
    n_ops[m] = 2
    for c, op in ((3, 1), (4, 2)):
        m = cls == c
        ops[m, 0], lens[m, 0] = 0, a_ind[m]
        ops[m, 1], lens[m, 1] = op, b_ind[m]
        ops[m, 2] = 0
        lens[m, 2] = read_len - a_ind[m] - (b_ind[m] if op == 1 else 0)
        n_ops[m] = 3
    return ops, lens, n_ops


def _cigar_text(ops, lens, n_ops):
    """CIGAR strings as ``(mat, lens)`` pieces, one per op slot."""
    pieces = []
    letters = np.frombuffer(b"MIDNSHP=X", dtype=np.uint8)
    for k in range(3):
        present = k < n_ops
        d, w = _vdigits(lens[:, k], 3)
        pieces.append((d, np.where(present, w, 0)))
        pieces.append((letters[ops[:, k]][:, None], present.astype(np.int64)))
    return pieces


def _ref_len(ops, lens, n_ops):
    k = np.arange(3)[None, :] < n_ops[:, None]
    consumes = (ops == 0) | (ops == 2)
    return (lens * (k & consumes)).sum(axis=1)


class Design:
    """The configuration's fixed genome features: contigs, hot spots,
    capture targets."""

    def __init__(self, cfg: dict) -> None:
        self.contigs = [(str(n), int(length)) for n, length in cfg["contigs"]]
        lengths = np.array([c[1] for c in self.contigs], dtype=np.int64)
        self.contig_p = lengths / lengths.sum()
        self.contig_len = lengths
        rng = _rng(int(cfg["design_seed"]))
        big = np.where(lengths >= 10**7, lengths, 0)
        big_p = big / big.sum()
        hs = cfg.get("hotspots", {"share": 0.0, "count": 0, "width": 0})
        self.hot_share = float(hs["share"])
        nh = int(hs["count"])
        self.hot_ref = rng.choice(len(lengths), size=nh, p=big_p)
        self.hot_pos = (rng.random(nh) * (lengths[self.hot_ref] - 2e6)).astype(np.int64) + 10**6
        self.hot_width = int(hs["width"])
        cap = cfg.get("capture")
        self.targets = None
        if cap:
            nt = int(cap["targets"])
            t_ref = rng.choice(len(lengths), size=nt, p=big_p)
            t_pos = (rng.random(nt) * (lengths[t_ref] - 2e6)).astype(np.int64) + 10**6
            t_len = rng.integers(cap["length"][0], cap["length"][1] + 1, nt)
            eff = rng.lognormal(0.0, float(cap["efficiency_sigma"]), nt)
            self.targets = (t_ref, t_pos, t_len, eff / eff.sum())
            self.on_target = float(cap["on_target_share"])
            self.padding = int(cap["padding"])


def _positions(rng, design: Design, n: int, read_len: int, span: np.ndarray):
    """Leftmost mate's contig and 0-based start, for ``n`` pairs."""
    ref = rng.choice(len(design.contig_len), size=n, p=design.contig_p)
    room = design.contig_len[ref] - span - read_len - 1
    pos = (rng.random(n) * room).astype(np.int64)
    if design.hot_share > 0:
        hot = rng.random(n) < design.hot_share
        k = rng.integers(0, len(design.hot_pos), n)
        ref = np.where(hot, design.hot_ref[k], ref)
        pos = np.where(hot, design.hot_pos[k] + rng.integers(0, design.hot_width, n), pos)
    if design.targets is not None:
        t_ref, t_pos, t_len, t_p = design.targets
        on = rng.random(n) < design.on_target
        k = rng.choice(len(t_ref), size=n, p=t_p)
        off = rng.integers(-design.padding, 1, n) + (
            rng.random(n) * (t_len[k] + design.padding)
        ).astype(np.int64)
        ref = np.where(on, t_ref[k], ref)
        pos = np.where(on, np.maximum(t_pos[k] + off, 0), pos)
    return ref, pos


def _chunk(cfg: dict, design: Design, seed: int, ci: int, n_pairs: int):
    read_len = int(cfg["read_length"])
    lay = _rng(int(cfg["layout_seed"]), ci)
    rng = _rng(seed, ci)
    n = 2 * n_pairs
    mate = np.tile([0, 1], n_pairs)  # 0 = first of pair
    pair = np.repeat(np.arange(n_pairs), 2)

    # ---- layout: every size in the chunk --------------------------------
    u = lay.random(n_pairs)
    um = cfg["unmapped"]
    both_un = u < um["pair_share"]
    one_un = (~both_un) & (u < um["pair_share"] + um["mate_share"])
    un_mate = lay.integers(0, 2, n_pairs)  # which mate is unmapped
    dup = (lay.random(n_pairs) < cfg["duplicate_share"]) & ~both_un & ~one_un
    src = np.arange(n_pairs)
    cand = np.flatnonzero(~dup & ~both_un & ~one_un)
    if len(cand):
        src[dup] = cand[lay.integers(0, len(cand), int(dup.sum()))]
    ops, clen, n_ops = _cigar_layout(lay, n, read_len, cfg["cigar_mix"])
    mism = np.minimum(lay.poisson(cfg["mismatches_mean"], n), 4)
    mpos = np.sort(lay.random((n, 4)), axis=1)
    digit5 = lay.random((n_pairs, 2)) < 0.7  # name x/y: 5 digits vs 4
    xa = lay.random(n) < cfg["xa_share"]
    sa = lay.random(n) < cfg["sa_share"]
    alt_ref = lay.integers(0, len(design.contigs), (n, 2))
    alt_pos9 = lay.random((n, 2)) < 0.5
    rsrc = 2 * src[pair] + mate  # the record whose layout this one copies
    ops, clen, n_ops, mism, mpos = ops[rsrc], clen[rsrc], n_ops[rsrc], mism[rsrc], mpos[rsrc]
    unmapped = np.repeat(both_un, 2) | (np.repeat(one_un, 2) & (mate == np.repeat(un_mate, 2)))
    mate_unmapped = np.repeat(both_un, 2) | (np.repeat(one_un, 2) & (mate != np.repeat(un_mate, 2)))
    n_ops = np.where(unmapped, 0, n_ops)
    is_del = (n_ops == 3) & (ops[:, 1] == 2)
    mism = np.where(unmapped | is_del, 0, mism)
    xa &= ~unmapped
    sa &= ~unmapped
    mate_rec = np.arange(n) ^ 1

    # ---- contents: drawn from the seed ------------------------------------
    insert = np.clip(
        rng.normal(cfg["insert_mean"], cfg["insert_sd"], n_pairs), read_len, 3 * cfg["insert_mean"]
    ).astype(np.int64)
    ref, left = _positions(rng, design, n_pairs, read_len, insert)
    r1_left = rng.random(n_pairs) < 0.5
    ref, left, insert, r1_left = ref[src], left[src], insert[src], r1_left[src]
    right = left + insert - read_len
    is_left = (mate == 0) == np.repeat(r1_left, 2)
    refid = np.repeat(ref, 2)
    pos = np.where(is_left, np.repeat(left, 2), np.repeat(right, 2))
    mate_pos_mapped = pos[mate_rec]
    # An unmapped mate sits at its mapped mate's place; both unmapped: none.
    pos = np.where(unmapped, mate_pos_mapped, pos)
    pos = np.where(np.repeat(both_un, 2), -1, pos)
    refid = np.where(np.repeat(both_un, 2), -1, refid)
    rlen = _ref_len(ops, clen, n_ops)
    end = np.where(unmapped, pos + 1, pos + rlen)
    bins = np.where(np.repeat(both_un, 2), 4680, reg2bin(np.maximum(pos, 0), end))
    mapq = np.where(rng.random(n) < 0.9, 60, rng.integers(0, 60, n))
    mapq = np.where(unmapped, 0, mapq)
    reverse = ~is_left & ~unmapped
    flag = 1 | np.where(mate == 0, 64, 128)
    flag |= np.where(unmapped, 4, 0) | np.where(mate_unmapped, 8, 0)
    flag |= np.where(~unmapped & ~mate_unmapped, 2, 0)
    flag |= np.where(reverse, 16, 0) | np.where(reverse[mate_rec], 32, 0)
    tlen = np.where(
        unmapped | mate_unmapped, 0, np.where(is_left, 1, -1) * np.repeat(insert, 2)
    )
    next_refid = refid[mate_rec]
    next_pos = pos[mate_rec]

    bases = _BASES[rng.integers(0, 4, (n, read_len), dtype=np.uint8)]
    u = rng.random((n, read_len), dtype=np.float32)
    bases[u < 0.001] = 15  # N
    qual = np.array([37, 23, 12, 2], np.uint8)[
        np.searchsorted(np.cumsum(cfg["quality_bins"])[:-1], rng.random((n, read_len), dtype=np.float32))
    ]
    tail = rng.random(n) < 0.03
    tail_from = read_len - rng.integers(5, 40, n)
    qual[tail[:, None] & (np.arange(read_len)[None, :] >= tail_from[:, None])] = 2
    dup_rec = np.repeat(dup, 2)
    bases[dup_rec] = bases[rsrc[dup_rec]]

    # ---- bytes --------------------------------------------------------------
    lane = rng.integers(1, 5, n_pairs)
    tile = rng.integers(1, 3, n_pairs) * 1000 + rng.integers(1, 7, n_pairs) * 100 + rng.integers(1, 79, n_pairs)
    lo5, hi5 = 10000, 32000
    x = np.where(digit5[:, 0], rng.integers(lo5, hi5, n_pairs), rng.integers(1000, 10000, n_pairs))
    y = np.where(digit5[:, 1], rng.integers(lo5, hi5, n_pairs), rng.integers(1000, 10000, n_pairs))
    name_pieces = [(_const(cfg["name_prefix"].encode(), n), None)]
    for v, w in ((lane, 1), (tile, 4), (x, 5), (y, 5)):
        d, dl = _vdigits(np.repeat(v, 2), w)
        name_pieces += [(d, dl), (_const(b":", n), None)]
    name_pieces[-1] = (np.zeros((n, 1), np.uint8), None)  # NUL, not ':'
    l_name = sum(
        (np.full(n, m.shape[1]) if ln is None else ln) for m, ln in name_pieces
    )

    rows = _Rows(n)
    fixed = np.zeros(
        n,
        dtype=np.dtype(
            [("refid", "<i4"), ("pos", "<i4"), ("l_name", "u1"), ("mapq", "u1"),
             ("bin", "<u2"), ("n_cigar", "<u2"), ("flag", "<u2"), ("l_seq", "<u4"),
             ("next_refid", "<i4"), ("next_pos", "<i4"), ("tlen", "<i4")]
        ),
    )
    fixed["refid"], fixed["pos"], fixed["l_name"], fixed["mapq"] = refid, pos, l_name, mapq
    fixed["bin"], fixed["n_cigar"], fixed["flag"], fixed["l_seq"] = bins, n_ops, flag, read_len
    fixed["next_refid"], fixed["next_pos"], fixed["tlen"] = next_refid, next_pos, tlen
    rows.add(fixed.view(np.uint8).reshape(n, 32))
    for m, ln in name_pieces:
        rows.add(m, ln)
    cig = (clen << 4) | ops
    rows.add(_le(cig.ravel(), np.uint32).reshape(n, 12), 4 * n_ops)
    packed = (bases[:, 0::2] << 4) | (bases[:, 1::2] if read_len % 2 == 0 else
                                      np.pad(bases[:, 1::2], ((0, 0), (0, 1))))
    rows.add(packed)
    rows.add(qual)

    mapped = ~unmapped
    nm = mism + np.where(n_ops == 3, clen[:, 1], 0)
    rows.add(np.concatenate([_const(b"NMC", n), nm[:, None]], 1), present=mapped)
    rows.add_z(b"MD", _md_pieces(rng, ops, clen, n_ops, mism, mpos), present=mapped)
    m_ops, m_clen, m_n = ops[mate_rec], clen[mate_rec], n_ops[mate_rec]
    rows.add_z(b"MC", _cigar_text(m_ops, m_clen, m_n), present=~mate_unmapped)
    score = np.clip(read_len - 5 * nm - rng.integers(0, 10, n), 0, 255)
    score = np.where(unmapped, 0, score)
    sub = np.where(unmapped, 0, (score * rng.random(n)).astype(np.int64))
    rows.add(np.concatenate([_const(b"ASC", n), score[:, None]], 1))
    rows.add(np.concatenate([_const(b"XSC", n), sub[:, None]], 1))
    for tag, present, k in ((b"XA", xa, 0), (b"SA", sa, 1)):
        rows.add_z(tag, _alt_pieces(rng, design, alt_ref[:, k], alt_pos9[:, k], ops, clen, n_ops, tag == b"SA"), present=present)
    rows.add_z(b"RG", [(_const(cfg["read_group"].encode(), n), None)])
    rows.add(np.concatenate([_const(b"MQC", n), mapq[mate_rec][:, None]], 1), present=~mate_unmapped)
    ms = qual[mate_rec].astype(np.int64).sum(axis=1)
    rows.add(np.concatenate([_const(b"msS", n), _le(ms, np.uint16)], 1))
    return rows.assemble()


def _md_pieces(rng, ops, clen, n_ops, mism, mpos):
    """``MD:Z`` text ``r0 X r1 X ... rk``: run lengths from the layout,
    mismatch bases from the seed.  A deletion gives ``a^<bases>c``."""
    n = len(ops)
    aligned = clen[:, 0].copy()
    aligned = np.where((n_ops == 2) & (ops[:, 0] == 4), clen[:, 1], aligned)
    aligned = np.where(n_ops == 3, clen[:, 0] + clen[:, 2], aligned)
    is_del = (n_ops == 3) & (ops[:, 1] == 2)
    free = np.maximum(aligned - mism, 0)
    cuts = np.sort((mpos * free[:, None]).astype(np.int64), axis=1)
    cuts = np.where(np.arange(4)[None, :] < mism[:, None], cuts, free[:, None])
    runs = np.diff(np.concatenate([np.zeros((n, 1), np.int64), cuts], 1), axis=1)
    runs = np.concatenate([runs, (free - cuts[:, 3])[:, None]], 1)
    run0 = np.where(is_del, clen[:, 0], np.where(mism == 0, aligned, runs[:, 0]))
    d, w = _vdigits(run0, 3)
    pieces = [(d, w)]
    for j in range(1, 5):
        live = j <= mism
        pieces.append((_LETTERS[rng.integers(0, 4, n)][:, None], live.astype(np.int64)))
        d, w = _vdigits(runs[:, j], 3)
        pieces.append((d, np.where(live, w, 0)))
    del_mat = np.concatenate([_const(b"^", n), _LETTERS[rng.integers(0, 4, (n, 9))]], 1)
    pieces.append((del_mat, np.where(is_del, 1 + clen[:, 1], 0)))
    d, w = _vdigits(clen[:, 2], 3)
    pieces.append((d, np.where(is_del, w, 0)))
    return pieces


def _alt_pieces(rng, design, alt_ref, pos9, ops, clen, n_ops, supplementary):
    """``XA:Z``/``SA:Z`` text: ``chr,+pos,CIGAR,NM;`` (``SA`` adds MAPQ)."""
    n = len(alt_ref)
    names = [c[0].encode() for c in design.contigs]
    w = max(len(s) for s in names)
    table = np.zeros((len(names), w), np.uint8)
    for i, s in enumerate(names):
        table[i, : len(s)] = np.frombuffer(s, np.uint8)
    name_len = np.array([len(s) for s in names])
    apos = np.where(pos9, rng.integers(10**8, 2 * 10**8, n), rng.integers(10**7, 10**8, n))
    strand = np.where(rng.random(n) < 0.5, ord("+"), ord("-")).astype(np.uint8)[:, None]
    pieces = [(table[alt_ref], name_len[alt_ref]), (_const(b",", n), None), (strand, None)]
    d, dw = _vdigits(apos, 9)
    pieces += [(d, dw), (_const(b",", n), None)]
    pieces += _cigar_text(ops, clen, np.maximum(n_ops, 1))
    pieces.append((_const(b",", n), None))
    if supplementary:
        d, dw = _vdigits(rng.integers(10, 60, n), 2)
        pieces += [(d, dw), (_const(b",", n), None)]
    pieces += [((48 + rng.integers(0, 10, n)).astype(np.uint8)[:, None], None),
               (_const(b";", n), None)]
    return pieces


def header_text(cfg: dict) -> str:
    lines = ["@HD\tVN:1.6\tSO:unsorted"]
    lines += [f"@SQ\tSN:{n}\tLN:{length}" for n, length in cfg["contigs"]]
    lines.append(cfg["rg_line"])
    lines += cfg.get("pg_lines", [])
    return "\n".join(lines) + "\n"


def generate(cfg: dict, records: int, seed: int) -> Corpus:
    """``records`` reads (an even number) of the configuration, contents
    drawn from ``seed``."""
    if records % 2:
        raise ValueError("paired reads: records must be even")
    design = Design(cfg)
    pairs = records // 2
    starts = range(0, pairs, CHUNK_PAIRS)
    with ThreadPoolExecutor(max(1, min(8, os.cpu_count() or 1))) as pool:
        parts = list(pool.map(
            lambda c: _chunk(cfg, design, seed, c[0], min(CHUNK_PAIRS, pairs - c[1])),
            enumerate(starts),
        ))
    streams = [s for s, _ in parts]
    lens = [ln for _, ln in parts]
    lengths = np.concatenate(lens)
    offsets = np.zeros(len(lengths), np.int64)
    np.cumsum(lengths[:-1], out=offsets[1:])
    return Corpus(
        header_text=header_text(cfg),
        refs=[(str(n), int(length)) for n, length in cfg["contigs"]],
        stream=np.concatenate(streams),
        offsets=offsets,
        lengths=lengths,
    )


def write(corpus: Corpus, path: str, level: int) -> dict:
    """Writes ``corpus`` as a BAM file at zlib ``level``; returns the input
    work a job needs (the record members' deflated and inflated bytes) and
    what the file holds."""
    w = bgzf.write_bam(path, bgzf.header_block(corpus.header_text, corpus.refs), corpus.stream,
                       corpus.offsets, corpus.lengths, level)
    return {"input_compressed": w.record_compressed, "input_inflated": w.record_inflated,
            "file_compressed": w.compressed_bytes, "file_inflated": w.inflated_bytes,
            "members": w.members}
