"""Lockstep-lane DEFLATE encoder (ops/pallas/deflate_lanes.py): native
zlib is the external oracle throughout — every compressed member must
inflate byte-exact through ``zlib.decompressobj(-15)`` AND through the
lanes decoder (``inflate_lanes``), the two consumers the part-write path
feeds.  The kernel runs in interpret mode on CPU.

Split per the CI contract: fast oracle coverage (the corpus the ISSUE
names: BAM-like records, incompressible bytes, zero runs, empty member,
cap-boundary member, overflow tier-down) always runs; the heavier fuzz
rides the ``slow`` mark; the real-chip test rides ``tpu`` +
``device_deflate`` (conftest skips it under JAX_PLATFORMS=cpu).
"""

import hashlib
import io
import os
import struct
import zlib

import numpy as np
import pytest

import jax.numpy as jnp

from hadoop_bam_tpu.conf import Configuration, DEFLATE_LANES
from hadoop_bam_tpu.ops import flate
from hadoop_bam_tpu.ops.pallas.deflate_lanes import (
    bench_deflate_ratio,
    deflate_lanes,
)
from hadoop_bam_tpu.ops.pallas.inflate_lanes import inflate_lanes
from hadoop_bam_tpu.spec import bgzf

LANES_CONF = Configuration({DEFLATE_LANES: "true"})


def _encode(payloads, **kw):
    P = max(max((len(p) for p in payloads), default=1), 1)
    mat = np.zeros((len(payloads), P), np.uint8)
    lens = np.zeros(len(payloads), np.int32)
    for i, p in enumerate(payloads):
        mat[i, : len(p)] = np.frombuffer(p, np.uint8)
        lens[i] = len(p)
    return deflate_lanes(mat, lens, interpret=True, **kw)


def _assert_both_oracles(payloads, **kw):
    """Round-trip every member through native zlib AND the lanes decoder."""
    comp, clens, ok = _encode(payloads, **kw)
    assert ok.all(), ok
    for i, p in enumerate(payloads):
        d = zlib.decompressobj(-15)
        out = d.decompress(comp[i, : clens[i]].tobytes())
        assert out == p, f"zlib mismatch member {i}"
        assert d.eof, f"member {i} stream did not terminate"
    isz = np.asarray([len(p) for p in payloads], np.int32)
    out2, ok2 = inflate_lanes(
        comp[:, : max(int(clens.max()), 1)], clens.astype(np.int32), isz,
        interpret=True,
    )
    assert ok2.all(), ok2
    for i, p in enumerate(payloads):
        assert out2[i, : len(p)].tobytes() == p, f"lanes mismatch member {i}"
    return comp, clens


def test_oracle_corpus():
    """The ISSUE's fast corpus in one batch (one kernel geometry): BAM-like
    records, incompressible random bytes, an all-zero run, an empty
    member, and a tiny member — cross-checked through both decoders."""
    rng = np.random.default_rng(0)
    rec = (
        struct.pack("<I", 44)
        + struct.pack("<iiBBHHHiiii", 0, 1000, 5, 60, 4681, 1, 0, -1, -1, 0, 0)
        + b"r01\x00" + bytes(8)
    )
    payloads = [
        (rec * 12)[:500],                                     # BAM-like
        bytes(rng.integers(0, 256, 400, dtype=np.uint8)),     # incompressible
        b"\x00" * 480,                                        # zero run
        b"",                                                  # empty
        b"ACG",                                               # below MIN_MATCH
    ]
    comp, clens = _assert_both_oracles(payloads)
    assert clens[0] < len(payloads[0]) // 2   # matches actually found
    assert clens[2] < 16                      # RLE-style overlap copies
    assert clens[3] == 2                      # empty fixed block


def test_member_at_payload_cap_boundary():
    """A member exactly at its pow2 geometry bucket boundary (the padded
    row has zero slack): matches may end exactly at the member edge."""
    pat = b"0123456789ABCDEF" * 16
    payloads = [pat * 2, (pat * 2)[:500]]  # 512 == bucket floor exactly
    assert len(payloads[0]) == 512
    _assert_both_oracles(payloads)


def test_output_overflow_tiers_down_ok0():
    """Members whose compressed size exceeds the caller's budget come back
    ok=0 (tier-down signal) without poisoning batch mates."""
    rng = np.random.default_rng(1)
    rand = bytes(rng.integers(0, 256, 300, dtype=np.uint8))
    comp, clens, ok = _encode([rand, b"easy " * 60], max_clen=100)
    assert not ok[0] and ok[1], (ok, clens)


def test_geometry_past_member_cap_declines():
    """The streaming geometry accepts full-size BGZF payloads (the old
    32 KiB whole-member cap is gone); only members past the 64 KiB token
    domain decline — cheaply, before any launch."""
    from hadoop_bam_tpu.ops.pallas.deflate_lanes import _MAX_MEMBER, accepts

    assert accepts(1 << 15)[0]          # old cap now well inside the tier
    assert accepts(_MAX_MEMBER)[0]
    n = _MAX_MEMBER + 8
    mat = np.zeros((1, n), np.uint8)
    _, _, ok = deflate_lanes(mat, np.array([n], np.int32), interpret=True)
    assert not ok[0]


def test_geometry_past_vmem_budget_declines(monkeypatch):
    from hadoop_bam_tpu.ops.pallas import deflate_lanes as dl_mod

    monkeypatch.setattr(dl_mod, "_VMEM_BUDGET_BYTES", 1 << 10)
    mat = np.zeros((1, 2048), np.uint8)
    _, _, ok = deflate_lanes(
        mat, np.array([2048], np.int32), interpret=True
    )
    assert not ok[0]


def test_ratio_bam_like_within_bound_of_zlib1():
    """Acceptance bound: the LZ77 emit must land within 1.25x of zlib
    level-1 on the BAM-like corpus (the literal-only tier fails this)."""
    r = bench_deflate_ratio(n_members=2, member=2048, interpret=True)
    assert r["n_ok"] == 2, r
    assert r["rel_zlib1"] <= 1.25, r
    # Premise: literal-only fixed-Huffman cannot meet the bound.
    assert 9 / 8 > 1.25 * r["zlib1_ratio"]


def _random_members(seed: int, n: int, lo: int, hi: int):
    """Seeded member payloads of three kinds (random bytes, a 4-letter
    alphabet, a SAM-text alphabet); member 0 is empty."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi, n).astype(np.int32)
    lens[0] = 0
    alpha = np.frombuffer(b"ACGTN\t:0123", np.uint8)
    mat = np.zeros((n, int(lens.max())), np.uint8)
    for i, n_i in enumerate(lens):
        kind = i % 3
        if kind == 0:
            p = rng.integers(0, 256, n_i, dtype=np.uint8)
        elif kind == 1:
            p = rng.integers(65, 69, n_i, dtype=np.uint8)
        else:
            p = alpha[rng.integers(0, len(alpha), n_i)]
        mat[i, :n_i] = p
    return mat, lens


def _golden_case(name: str):
    """(payload rows, lengths, chunk bytes): every member crosses at least
    two input chunks."""
    from hadoop_bam_tpu.ops.pallas.deflate_lanes import _bam_like_corpus

    if name == "bam_like":
        return _bam_like_corpus(6, 2600), np.full(6, 2600, np.int32), 1024
    if name == "random":
        return _random_members(2025, 7, 1500, 3300) + (1024,)
    return _bam_like_corpus(2, 4600), np.full(2, 4600, np.int32), 4096


#: Each member's (length, compressed length, SHA-256 of its bytes), as the
#: XLA token compaction and scatter-add emit wrote them: the pack kernel
#: must give the same bits for the same tokens.
GOLDEN = {
    "bam_like": [
        (2600, 206, "3f538dac190d8940feaba8cc10337abe7fdd957af078d61503b0c037f9bd1a84"),
        (2600, 192, "becf9634fc11b9693dfd3287cf6bbbaa808e933a9726a326d81096c127dae48e"),
        (2600, 201, "0f2477b0ee11f73be8848f0adc216c3fbbd12e17e7e0c840cae1a66aaecfce7d"),
        (2600, 192, "8e0b76375b3b4bd6a0424604cb04a28313f34abeb1af0a5677a3aafde22747e9"),
        (2600, 202, "2c9e65a43956fe88c27da7d89b8f11106a8d554547f522d54224dc63d1d338b5"),
        (2600, 198, "96f34e5b1e5fbbebe2b1e34f8ea9fd7072c2d62b723caaca8983d8c42091fe75"),
    ],
    "random": [
        (0, 2, "9b4fb24edd6d1d8830e272398263cdbf026b97392cc35387b991dc0248a628f9"),
        (3290, 1937, "c745e0e3f868950cff7d05ad66ad67866b7a4035bfabe75577b450adf3f43b8e"),
        (3286, 2980, "c7f4e99fb78ffdc6476b03918e0b79108b3534e502e6e92c09350b9cdd1661c4"),
        (2187, 2305, "6c086346e713a2b1697f75acdbc136c4f69b3f0a42940134081b1f866f19076b"),
        (3216, 1871, "1b9252cacb5bb575681091038e0724ed20fb1ee49dc3423af03aa0ecffaa8bc8"),
        (2988, 2703, "18d8c57cf08786b849dbea0ee24119d744b59e2bc75433ece35e7e103d0378a6"),
        (2651, 2799, "699ccff5652a7c203bb11744d5985cd84baee85d18e09560218fdd6b18e3a57d"),
    ],
    "bam_like_4k_chunks": [
        (4600, 325, "23ebe7af032ecddbfc85fc7cc15e7bde096b386cd7434579eb0c4a0d436693d3"),
        (4600, 322, "3047930707a84ed7fb338aedb2c078e0feca2ed32755e353f942d7d289b9669f"),
    ],
}


@pytest.mark.parametrize("case", sorted(GOLDEN))
def test_members_match_golden_bytes(case):
    """Byte parity with the recorded encoder output, member by member."""
    mat, lens, chunk = _golden_case(case)
    comp, clens, ok = deflate_lanes(
        mat, lens, chunk_bytes=chunk, interpret=True
    )
    assert ok.all(), ok
    got = [
        (int(lens[i]), int(clens[i]),
         hashlib.sha256(comp[i, : clens[i]].tobytes()).hexdigest())
        for i in range(len(lens))
    ]
    assert got == GOLDEN[case]


# --------------------------------------------------------------------------
# The pack kernel alone, on hand-built token tiles.
# --------------------------------------------------------------------------

_PACK_TILE = 40     # token rows a step
_PACK_CHUNKS = 3


def _tok(t) -> int:
    return t[1] if t[0] == "lit" else (1 << 30) | (t[1] << 16) | t[2]


def _straddle_prefix(shift: int):
    """Literals whose codes (8 bits below 144, 9 from 144) put the next
    code at bit ``shift`` of a word, after the 3 header bits."""
    for n9 in range(32):
        for n8 in range(32):
            if (3 + 8 * n8 + 9 * n9) % 32 == shift:
                return [("lit", 65)] * n8 + [("lit", 200)] * n9
    raise AssertionError(shift)


def _pack_lanes():
    """Per lane: its tokens and how many of them each step's tile holds."""
    lanes = [
        ([], [0, 0, 0]),                                     # no tokens
        ([("lit", 7), ("copy", 4, 1)], [2, 0, 0]),           # ends early
        ([("lit", 0), ("lit", 143), ("lit", 144), ("lit", 255)], [1, 2, 1]),
        ([("copy", 4, 1), ("copy", 227, 24577), ("copy", 257, 32768),
          ("copy", 258, 1), ("lit", 1), ("copy", 258, 32768)], [3, 0, 3]),
    ]
    rng = np.random.default_rng(5)
    # Streams across both tile boundaries, carried bits never aligned.
    for _ in range(4):
        toks = []
        for _ in range(int(rng.integers(60, 3 * _PACK_TILE))):
            if rng.random() < 0.4:
                toks.append(("copy", int(rng.integers(3, 259)),
                             int(rng.integers(1, 32769))))
            else:
                toks.append(("lit", int(rng.integers(0, 256))))
        a = int(rng.integers(len(toks) - 2 * _PACK_TILE, _PACK_TILE + 1))
        b = int(rng.integers(len(toks) - a - _PACK_TILE, _PACK_TILE + 1))
        lanes.append((toks, [a, b, len(toks) - a - b]))
    # A 31-bit code (length 257: 8 + 5 bits, distance 32768: 5 + 13) at
    # every bit shift of a word, split over the first two steps.
    for shift in range(32):
        toks = _straddle_prefix(shift) + [("copy", 257, 32768), ("lit", 9)]
        lanes.append((toks, [len(toks) - 2, 2, 0]))
    return lanes


def test_pack_kernel_matches_host_encoder():
    """Hand-built token tiles straight into the pack kernel: every lane's
    bytes equal ``flate.encode_tokens_fixed`` of its tokens."""
    from hadoop_bam_tpu.ops.pallas import deflate_lanes as dl

    lanes = _pack_lanes()
    n = len(lanes)
    toks = np.zeros((_PACK_CHUNKS * _PACK_TILE, dl.LANES), np.int32)
    cnts = np.zeros((_PACK_CHUNKS * dl._CNT_ROWS, dl.LANES), np.int32)
    for j, (tl, split) in enumerate(lanes):
        assert sum(split) == len(tl) and max(split) <= _PACK_TILE
        i = 0
        for k, c in enumerate(split):
            rows = [_tok(t) for t in tl[i : i + c]]
            toks[k * _PACK_TILE : k * _PACK_TILE + c, j] = rows
            cnts[k * dl._CNT_ROWS : (k + 1) * dl._CNT_ROWS, j] = c
            i += c
    word_tile = dl._round_up((31 + 31 * _PACK_TILE + 7) // 32 + 2, 8)
    words, counts, cl = dl._deflate_pack_kernel(
        jnp.asarray(toks), jnp.asarray(cnts), tok_tile=_PACK_TILE,
        word_tile=word_tile, n_chunks=_PACK_CHUNKS, lanes_out=dl.LANES,
        interpret=True,
    )
    counts_np = np.asarray(counts)
    want = [flate.encode_tokens_fixed(tl) for tl, _ in lanes]
    out_bytes = max(len(w) for w in want)
    comp, fits = dl._join_words(
        np.asarray(words), counts_np, n, word_tile, out_bytes
    )
    assert fits.all()
    clens = np.asarray(cl)[0, :n]
    for j, w in enumerate(want):
        assert int(clens[j]) == len(w), j
        assert comp[j, : clens[j]].tobytes() == w, j
    # Each step's token counts come back beside its word counts.
    assert (counts_np[1 :: dl._CNT_ROWS, :n] == cnts[:: dl._CNT_ROWS, :n]).all()


class TestBgzfCompressDevice:
    def test_level0_emits_stored_blocks(self):
        rng = np.random.default_rng(2)
        data = rng.integers(0, 256, 5000, dtype=np.uint8).tobytes()
        blob = flate.bgzf_compress_device(data, level=0, block_payload=2048)
        assert bgzf.decompress_all(blob) == data
        from hadoop_bam_tpu import native

        co, _, _ = native.scan_blocks(np.frombuffer(blob, np.uint8))
        for c in co[:-1]:  # skip the empty terminator member
            first = blob[int(c) + 18]
            assert first & 7 == 1, "stored final block expected"

    def test_level0_empty_stream(self):
        blob = flate.bgzf_compress_device(b"", level=0)
        assert bgzf.decompress_all(blob) == b""

    def test_lanes_tier_roundtrips_and_compresses(self):
        data = (b"@SQ\tSN:chr1\tLN:12345\n" * 150)[:3000]
        blob = flate.bgzf_compress_device(data, conf=LANES_CONF)
        assert bgzf.decompress_all(blob) == data
        lit = flate.bgzf_compress_device(data)  # literal tier (CPU auto)
        assert len(blob) < len(lit) // 2
        # The device decode chain reads its own encoder's output.
        assert flate.bgzf_decompress_device(blob, _force_no_host=True) == data

    def test_lanes_geometry_tierdown_to_host_zlib(self, monkeypatch):
        from hadoop_bam_tpu.ops.pallas import deflate_lanes as dl_mod
        from hadoop_bam_tpu.utils.tracing import METRICS

        data = b"tier down please " * 300  # one ~5.1 KB member
        before = METRICS.report()["counters"].get(
            "flate.deflate_lanes_tierdown", 0
        )
        # Shrink the VMEM budget so the (otherwise in-cap, post streaming
        # lift) geometry declines: every member must tier down to host
        # zlib, bit-faithfully, with the vmem reason counted.
        monkeypatch.setattr(dl_mod, "_VMEM_BUDGET_BYTES", 1 << 10)
        blob = flate.bgzf_compress_device(
            data, block_payload=24000, conf=LANES_CONF
        )
        assert bgzf.decompress_all(blob) == data
        after = METRICS.report()["counters"].get(
            "flate.deflate_lanes_tierdown", 0
        )
        assert after > before
        assert flate.LAST_DEFLATE_STATS.tierdown_vmem > 0
        assert flate.LAST_DEFLATE_STATS.lanes == 0

    def test_env_var_forces_tier_off(self, monkeypatch):
        monkeypatch.setenv("HBAM_DEFLATE_LANES", "0")
        assert not flate.deflate_lanes_tier_enabled(LANES_CONF)
        monkeypatch.setenv("HBAM_DEFLATE_LANES", "1")
        assert flate.deflate_lanes_tier_enabled(None)

    def test_conf_key_resolution(self):
        assert flate.deflate_lanes_tier_enabled(LANES_CONF)
        off = Configuration({DEFLATE_LANES: "false"})
        assert not flate.deflate_lanes_tier_enabled(off)
        # Unset + CPU backend: the local-latency auto rule declines.
        assert not flate.deflate_lanes_tier_enabled(Configuration())


class TestPartWritePath:
    def _mini_batch(self, n=90):
        from hadoop_bam_tpu.io.bam import BamInputFormat
        from hadoop_bam_tpu.spec import bam

        refs = [("chr1", 100000)]
        hdr = bam.BamHeader("@HD\tVN:1.6\n@SQ\tSN:chr1\tLN:100000", refs)
        rng = np.random.default_rng(4)
        recs = [
            bam.build_record(
                name=f"r{i:04d}", refid=0, pos=int(rng.integers(0, 90000)),
                mapq=60, flag=0, cigar=[(10, "M")], seq="ACGTACGTAC",
                qual=bytes([30] * 10),
            )
            for i in range(n)
        ]
        buf = io.BytesIO()
        w = bgzf.BgzfWriter(buf, level=1)
        w.write(hdr.encode())
        w.write(b"".join(r.encode() for r in recs))
        w.close()
        return hdr, buf.getvalue()

    def test_write_part_fast_device_parity(self, tmp_path):
        from hadoop_bam_tpu.io.bam import BamInputFormat, write_part_fast
        from hadoop_bam_tpu.spec import indices

        _, raw = self._mini_batch()
        p = tmp_path / "t.bam"
        p.write_bytes(raw)
        fmt = BamInputFormat()
        (split,) = fmt.get_splits([str(p)])
        batch = fmt.read_split(split)
        order = np.argsort(batch.keys, kind="stable")
        outs = {}
        for dev in (False, True):
            f, sb = io.BytesIO(), io.BytesIO()
            write_part_fast(
                f, batch, order=order, level=1,
                splitting_bai_stream=sb, device_deflate=dev,
            )
            outs[dev] = (f.getvalue(), sb.getvalue())
        host, dev = outs[False], outs[True]
        # Identical record content and order (framing legitimately differs).
        assert bgzf.decompress_all(
            host[0] + bgzf.TERMINATOR
        ) == bgzf.decompress_all(dev[0] + bgzf.TERMINATOR)

        # The splitting-bai entries must reference the same records.
        def rec_at(blob, voff):
            r = bgzf.BgzfReader(blob + bgzf.TERMINATOR)
            r.seek_voffset(voff)
            n = struct.unpack("<I", r.read_fully(4))[0]
            return r.read_fully(n)

        vh = indices.SplittingBai.load(host[1]).voffsets
        vd = indices.SplittingBai.load(dev[1]).voffsets
        assert len(vh) == len(vd)
        for a, b in zip(vh[:-1], vd[:-1]):
            assert rec_at(host[0], a) == rec_at(dev[0], b)

    def test_sort_bam_env_force_content_parity(self, tmp_path, monkeypatch):
        """Acceptance: sort_bam with HBAM_DEFLATE_LANES=1 produces parts
        whose merged content (records, order) is byte-identical to the
        host path, with a consistent splitting-bai."""
        from hadoop_bam_tpu.pipeline import sort_bam

        _, raw = self._mini_batch()
        src = tmp_path / "in.bam"
        src.write_bytes(raw)
        out_h = str(tmp_path / "host.bam")
        out_d = str(tmp_path / "dev.bam")
        sort_bam([str(src)], out_h, split_size=4096, level=1,
                 backend="host", write_splitting_bai=True)
        monkeypatch.setenv("HBAM_DEFLATE_LANES", "1")
        sort_bam([str(src)], out_d, split_size=4096, level=1,
                 backend="host", write_splitting_bai=True)
        bh = open(out_h, "rb").read()
        bd = open(out_d, "rb").read()
        assert bgzf.decompress_all(bh) == bgzf.decompress_all(bd)
        assert os.path.exists(out_d + ".splitting-bai")


@pytest.mark.slow
class TestFuzzZlibOracle:
    """Broader corpus: random shapes x content kinds, batched many per
    launch, both decode oracles per member."""

    def test_fuzz_shapes_and_kinds(self):
        rng = np.random.default_rng(7)
        payloads = []
        for t in range(24):
            n = int(rng.integers(1, 500))
            kind = t % 4
            if kind == 0:
                p = bytes(rng.integers(0, 256, n, dtype=np.uint8))
            elif kind == 1:
                p = (b"GATTACA-" * (n // 8 + 1))[:n]
            elif kind == 2:
                p = bytes(rng.integers(0, 4, n, dtype=np.uint8))
            else:
                p = bytes([int(rng.integers(0, 256))]) * n
            payloads.append(p)
        _assert_both_oracles(payloads)

    def test_fuzz_bam_like_members_larger(self):
        from hadoop_bam_tpu.ops.pallas.deflate_lanes import _bam_like_corpus

        mat = _bam_like_corpus(3, 2048)
        payloads = [mat[i].tobytes() for i in range(3)]
        _assert_both_oracles(payloads)

    def test_member_at_chunk_multiple(self):
        """A member exactly at a streaming-chunk multiple (zero padded
        slack in the last input tile; the full DEV_LZ_PAYLOAD blocking is
        covered on-chip by tests/test_stream_codecs.py's device_stream
        class — ~57 KiB is out of interpret-mode reach)."""
        pat = (b"part-write-cap!!" * 1024)[:8192]
        assert len(pat) == 8192
        _assert_both_oracles([pat])


@pytest.mark.tpu
@pytest.mark.device_deflate
def test_deflate_lanes_on_real_chip(capsys):
    """The compiled kernel on the chip, inside the sort it serves: a
    shrunk ``chip_smoke.py`` run in this process, which requires the
    device part writes (deflate lanes) to have encoded every part and
    the output to match the host oracle.  Skips off a TPU backend."""
    import json

    import jax

    if jax.default_backend() != "tpu":
        pytest.skip("this process's JAX backend is not a TPU")
    import chip_smoke

    assert chip_smoke.main(["--records", "200000"]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last)["rehearsal"] is True
