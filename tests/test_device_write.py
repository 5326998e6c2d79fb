"""Device-resident part writes (ISSUE 5): the on-chip sorted gather +
flag patch (ops/pallas/gather_stream.py), the device CRC32 kernel
(ops/pallas/crc32.py), and the ``device_input`` handoff that feeds the
deflate lanes straight from HBM — oracled against ``zlib.crc32``, the
host gather (+ ``patch_flags``), and the host-input compress path
byte-for-byte.

CI budget contract (see tests/test_stream_codecs.py): the always-on
cases run the interpret-mode encoder only on payloads ≤ ~3 KiB and all
share the default chunk geometry (one ``_deflate_match_kernel`` compile); the CRC and
gather programs are plain XLA and cheap everywhere.  Full-size blocking
rides ``slow`` + ``device_write`` (the conftest guard skips it under a
JAX_PLATFORMS=cpu pin).
"""

import io
import zlib

import numpy as np
import pytest

import jax.numpy as jnp

from hadoop_bam_tpu.conf import (
    Configuration,
    DEFLATE_LANES,
    INFLATE_LANES,
    WRITE_DEVICE,
)
from hadoop_bam_tpu.io.bam import (
    ChunkedRecords,
    RecordBatch,
    gather_record_array,
    patch_flags,
    write_part_fast,
)
from hadoop_bam_tpu.ops import flate
from hadoop_bam_tpu.ops.pallas.crc32 import crc32_device
from hadoop_bam_tpu.ops.pallas.gather_stream import gather_stream_device
from hadoop_bam_tpu.spec import bam, bgzf
from hadoop_bam_tpu.utils.tracing import METRICS

WRITE_CONF = Configuration(
    {WRITE_DEVICE: "true", DEFLATE_LANES: "true", INFLATE_LANES: "true"}
)


# --------------------------------------------------------------------------
# CRC32 kernel vs the zlib oracle.
# --------------------------------------------------------------------------


class TestCrc32Oracle:
    def test_fuzz_vs_zlib(self):
        """Empty, 1-byte, word-boundary, odd-tail and whole-stream
        windows — one batch, one launch geometry."""
        rng = np.random.default_rng(0)
        stream = rng.integers(0, 256, 3000, dtype=np.uint8)
        dev = jnp.asarray(stream)
        offs = np.array([0, 0, 10, 64, 100, 17, 2995, 0], dtype=np.int64)
        lens = np.array([0, 1, 4, 256, 123, 33, 5, 3000], dtype=np.int64)
        got = np.asarray(crc32_device(dev, offs, lens))
        want = np.array(
            [
                zlib.crc32(stream[o : o + l].tobytes()) & 0xFFFFFFFF
                for o, l in zip(offs, lens)
            ],
            dtype=np.uint32,
        )
        assert np.array_equal(got, want), (got, want)

    def test_member_blocking_windows(self):
        """The part writer's use: consecutive blocking cuts with a short
        final member (the chunk-boundary case) — plus the all-empty
        degenerate batch."""
        rng = np.random.default_rng(1)
        stream = rng.integers(0, 256, 2500, dtype=np.uint8)
        bp = 1024
        offs = np.arange(0, 2500, bp, dtype=np.int64)
        lens = np.minimum(bp, 2500 - offs)
        got = np.asarray(crc32_device(jnp.asarray(stream), offs, lens))
        for k, (o, l) in enumerate(zip(offs, lens)):
            assert got[k] == (
                zlib.crc32(stream[o : o + l].tobytes()) & 0xFFFFFFFF
            )
        empty = np.asarray(
            crc32_device(jnp.zeros((0,), jnp.uint8), [0], [0])
        )
        assert empty[0] == 0  # zlib.crc32(b"") == 0


# --------------------------------------------------------------------------
# Device gather + flag patch vs the host gather oracle.
# --------------------------------------------------------------------------


def _toy_batch(n=24, seed=2):
    """A RecordBatch-shaped record stream with residency attached; record
    bodies are synthetic but the size-word/extent geometry is real."""
    rng = np.random.default_rng(seed)
    parts = []
    offs, lens = [], []
    p = 0
    for i in range(n):
        body = rng.integers(0, 256, int(rng.integers(40, 90)), dtype=np.uint8)
        rec = np.concatenate(
            [
                np.frombuffer(
                    len(body).to_bytes(4, "little"), np.uint8
                ),
                body,
            ]
        )
        offs.append(p + 4)
        lens.append(len(body))
        p += len(rec)
        parts.append(rec)
    data = np.concatenate(parts)
    soa = {
        "rec_off": np.asarray(offs, np.int64),
        "rec_len": np.asarray(lens, np.int64),
    }
    return RecordBatch(
        soa=soa,
        data=data,
        keys=np.arange(n, dtype=np.int64),
        device_data=jnp.asarray(data),
    )


class TestDeviceGather:
    def test_matches_host_gather_with_markdup_flags(self):
        rng = np.random.default_rng(3)
        b = _toy_batch()
        n = b.n_records
        order = rng.permutation(n)
        dup = rng.random(n) < 0.4
        # Host oracle: gather then patch the sorted stream.
        host = gather_record_array(b, order).copy()
        ln = b.soa["rec_len"][order] + 4
        starts = np.cumsum(ln) - ln
        patch_flags(host, starts[dup[order]])
        src = b.soa["rec_off"][order] - 4
        out, total = gather_stream_device(
            b.device_data, src, ln, dup_mask=dup[order]
        )
        assert total == len(host)
        assert np.array_equal(np.asarray(out), host)

    def test_chunked_records_flat_residency(self):
        rng = np.random.default_rng(4)
        b1, b2 = _toy_batch(10, seed=5), _toy_batch(12, seed=6)
        ck = ChunkedRecords.from_batches(
            [b1, b2], with_keys=False, keep_device=True
        )
        assert ck.device_flat is not None
        n = ck.n_records
        order = rng.permutation(n)
        host = gather_record_array(ck, order)
        base = ck.chunk_base[ck.chunk_id.astype(np.int64)]
        src = (base + ck.soa["rec_off"] - 4)[order]
        ln = (ck.soa["rec_len"] + 4)[order]
        out, total = gather_stream_device(ck.device_flat, src, ln)
        assert np.array_equal(np.asarray(out), host)
        ck.release_device()
        assert ck.device_flat is None and ck.chunk_base is None

    def test_partial_residency_keeps_nothing(self):
        b1, b2 = _toy_batch(6, seed=7), _toy_batch(6, seed=8)
        b2.device_data = None
        ck = ChunkedRecords.from_batches(
            [b1, b2], with_keys=False, keep_device=True
        )
        assert ck.device_flat is None

    def test_int32_domain_declines(self):
        b = _toy_batch(4, seed=9)
        with pytest.raises(ValueError):
            gather_stream_device(
                b.device_data,
                np.array([2**31], dtype=np.int64),
                np.array([100], dtype=np.int64),
            )


# --------------------------------------------------------------------------
# The write path end to end: byte identity against the host gather path.
# --------------------------------------------------------------------------


class TestDeviceWritePart:
    def test_part_byte_identity_with_markdup_and_bai(self):
        """Sorted + markdup-flagged part: the device path (gather, patch,
        CRC, deflate all on chip) must emit the identical blob and the
        identical inline splitting-bai as the host gather + lanes path."""
        rng = np.random.default_rng(10)
        b = _toy_batch(30, seed=11)
        order = rng.permutation(b.n_records)
        dup = rng.random(b.n_records) < 0.3
        hb, hs = io.BytesIO(), io.BytesIO()
        write_part_fast(
            hb, b, order=order, level=1, device_deflate=True,
            device_write=False, dup_mask=dup, splitting_bai_stream=hs,
        )
        before = METRICS.report()["counters"].get(
            "bam.device_write_parts", 0
        )
        db, ds = io.BytesIO(), io.BytesIO()
        write_part_fast(
            db, b, order=order, level=1, device_write=True,
            dup_mask=dup, splitting_bai_stream=ds,
        )
        assert db.getvalue() == hb.getvalue()
        assert ds.getvalue() == hs.getvalue()
        assert (
            METRICS.report()["counters"]["bam.device_write_parts"]
            == before + 1
        )

    def test_multi_member_framing_device_crcs(self):
        """Several small members through the ``device_input`` compress:
        framing (BSIZE, CRC32, ISIZE per member) must match the host
        path bit-for-bit and decode through the BGZF oracle."""
        rng = np.random.default_rng(12)
        data = (
            (b"@CO\tdevice-resident-writes\n" * 60)[:1400]
            + bytes(rng.integers(0, 256, 1100, dtype=np.uint8))
        )
        dev = jnp.asarray(np.frombuffer(data, np.uint8))
        host = flate.bgzf_compress_device(
            data, level=1, block_payload=1024, use_lanes=True,
            append_terminator=False,
        )
        devb = flate.deflate_blocks_device(
            None, level=1, block_payload=1024, use_lanes=True,
            device_input=dev,
        )
        assert devb == host
        assert bgzf.decompress_all(devb + bgzf.TERMINATOR) == data
        assert flate.LAST_DEFLATE_STATS.lanes == 3

    def test_no_residency_tiers_down_with_reason(self):
        b = _toy_batch(8, seed=13)
        b.device_data = None
        before = METRICS.report()["counters"].get(
            "bam.device_write_tierdown.no_residency", 0
        )
        out = io.BytesIO()
        # Deflate lanes off: the tier-down lands on native zlib, so no
        # kernel compiles in this always-on case.
        write_part_fast(
            out, b, order=None, level=1, device_deflate=False,
            device_write=True,
        )
        assert len(out.getvalue()) > 0
        after = METRICS.report()["counters"][
            "bam.device_write_tierdown.no_residency"
        ]
        assert after == before + 1

    def test_external_sort_records_no_residency(self, tmp_path, monkeypatch):
        """The out-of-core bugfix: spill-run parts can never consume HBM
        residency — with the tier forced on, each range write must record
        ``no_residency`` instead of silently taking the host gather."""
        monkeypatch.setenv("HBAM_DEVICE_WRITE", "1")
        monkeypatch.setenv("HBAM_DEFLATE_LANES", "0")
        monkeypatch.setenv("HBAM_INFLATE_LANES", "0")
        from hadoop_bam_tpu.pipeline import sort_bam

        refs = [("chr1", 100000)]
        hdr = bam.BamHeader("@HD\tVN:1.6\n@SQ\tSN:chr1\tLN:100000", refs)
        rng = np.random.default_rng(14)
        buf = io.BytesIO()
        w = bgzf.BgzfWriter(buf, level=1)
        w.write(hdr.encode())
        for i in range(50):
            w.write(
                bam.build_record(
                    name=f"q{i:04d}", refid=0,
                    pos=int(rng.integers(0, 1000)), mapq=60, flag=0,
                    cigar=[(10, "M")], seq="ACGTACGTAC",
                    qual=bytes([30] * 10),
                ).encode()
            )
        w.close()
        src = tmp_path / "in.bam"
        src.write_bytes(buf.getvalue())
        before = METRICS.report()["counters"].get(
            "bam.device_write_tierdown.no_residency", 0
        )
        st = sort_bam(
            [str(src)], str(tmp_path / "out.bam"), level=1,
            backend="host", memory_budget=64 << 10,
        )
        assert st.n_records == 50
        after = METRICS.report()["counters"][
            "bam.device_write_tierdown.no_residency"
        ]
        assert after >= before + 1

    def test_transfers_ledger_reports_write_columns(self):
        from hadoop_bam_tpu.utils.tracing import transfers_report

        b = _toy_batch(10, seed=15)
        before = transfers_report().get("h2d.write_cols", 0)
        out = io.BytesIO()
        write_part_fast(out, b, order=None, level=1, device_write=True)
        rep = transfers_report()
        assert rep.get("h2d.write_cols", 0) > before
        assert rep.get("h2d_bytes", 0) >= rep.get("h2d.write_cols", 0)


@pytest.mark.slow
class TestDeviceWriteSortE2E:
    """Whole-pipeline byte identity with residency flowing read→write
    (device inflate leaves the split in HBM, the write gathers from it).
    Interpret-mode kernels: slow tier, small members throughout."""

    def _mini_bam(self, n=40):
        refs = [("chr1", 100000), ("chr2", 100000)]
        hdr = bam.BamHeader(
            "@HD\tVN:1.6\n@SQ\tSN:chr1\tLN:100000\n"
            "@SQ\tSN:chr2\tLN:100000",
            refs,
        )
        rng = np.random.default_rng(16)
        buf = io.BytesIO()
        w = bgzf.BgzfWriter(buf, level=1)
        w.write(hdr.encode())
        for i in range(n):
            w.write(
                bam.build_record(
                    name=f"q{i:04d}", refid=int(rng.integers(0, 2)),
                    pos=int(rng.integers(0, 1000)), mapq=60, flag=0,
                    cigar=[(10, "M")], seq="ACGTACGTAC",
                    qual=bytes([30] * 10),
                ).encode()
            )
        w.close()
        return buf.getvalue()

    def test_sort_bam_device_write_matches_host(self, tmp_path, monkeypatch):
        from hadoop_bam_tpu.pipeline import sort_bam

        src = tmp_path / "in.bam"
        src.write_bytes(self._mini_bam())
        monkeypatch.setenv("HBAM_DEVICE_PARSE", "0")
        monkeypatch.setenv("HBAM_INFLATE_LANES", "1")
        monkeypatch.setenv("HBAM_DEFLATE_LANES", "1")
        monkeypatch.setenv("HBAM_DEVICE_WRITE", "0")
        host_out = tmp_path / "host.bam"
        sort_bam([str(src)], str(host_out), level=1, backend="host")
        monkeypatch.setenv("HBAM_DEVICE_WRITE", "1")
        before = METRICS.report()["counters"].get(
            "bam.device_write_parts", 0
        )
        dev_out = tmp_path / "dev.bam"
        sort_bam([str(src)], str(dev_out), level=1, backend="host")
        assert dev_out.read_bytes() == host_out.read_bytes()
        assert (
            METRICS.report()["counters"].get("bam.device_write_parts", 0)
            > before
        )


@pytest.mark.slow
@pytest.mark.device_write
class TestFullSizeBlocking:
    """The acceptance corpus at the part writer's real blocking
    (``DEV_LZ_PAYLOAD`` ≈ 57 KiB members): byte identity of the
    device-input compress against the host path on a multi-member
    stream.  Needs a real chip — a full-size member is minutes of
    interpret emulation (conftest skips under the cpu pin)."""

    def test_full_size_device_input_identity(self):
        from hadoop_bam_tpu.ops.pallas.deflate_lanes import _bam_like_corpus

        data = _bam_like_corpus(1, 3 * flate.DEV_LZ_PAYLOAD + 1000)[
            0
        ].tobytes()
        dev = jnp.asarray(np.frombuffer(data, np.uint8))
        host = flate.bgzf_compress_device(
            data, level=1, use_lanes=True, append_terminator=False
        )
        devb = flate.deflate_blocks_device(
            None, level=1, use_lanes=True, device_input=dev
        )
        assert devb == host
        assert bgzf.decompress_all(devb + bgzf.TERMINATOR) == data
        assert flate.LAST_DEFLATE_STATS.lanes_hit_rate() == 1.0
