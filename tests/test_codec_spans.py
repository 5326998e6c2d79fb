"""Sub-spans inside the device codec stages and the device part write.

The benchmark reads device time and host waits per kernel family from
these spans (``benchmark/metrics/*``), so each test pins what one call
records: every sub-span, one ``*.wait`` per block of fetches of a
128-lane group, the stage spans unchanged.  CPU, interpret-mode kernels,
payloads of at most 3 KiB (the budget of tests/test_device_write.py).
"""

import re
import zlib

import numpy as np
import pytest

import jax.numpy as jnp

from hadoop_bam_tpu import native
from hadoop_bam_tpu.io.bam import RecordBatch
from hadoop_bam_tpu.ops import flate
from hadoop_bam_tpu.utils.tracing import METRIC_NAME_PATTERN, delta, snapshot

INFLATE_SPANS = (
    "flate.inflate.pack", "flate.inflate.launch", "flate.inflate.wait",
    "flate.inflate.verify", "flate.inflate.flatten",
)
DEFLATE_SPANS = (
    "flate.deflate.match", "flate.deflate.emit", "flate.deflate.wait",
    "flate.deflate.crc", "flate.deflate.frame",
)
WRITE_SPANS = ("bam.write.gather",)


@pytest.fixture(autouse=True)
def lanes_tiers(monkeypatch):
    monkeypatch.setenv("HBAM_INFLATE_LANES", "1")
    monkeypatch.setenv("HBAM_DEFLATE_LANES", "1")
    monkeypatch.setenv("HBAM_DEVICE_WRITE", "1")


def _payload(n: int, seed: int) -> bytes:
    rng = np.random.default_rng(seed)
    text = (b"@CO\tcodec-spans\n" * 200)[: n // 2]
    return text + bytes(rng.integers(0, 256, n - len(text), dtype=np.uint8))


def _members(data: bytes, block_payload: int):
    blob = flate.bgzf_compress_device(
        data, block_payload=block_payload, use_lanes=False, level=1
    )
    raw = np.frombuffer(blob, np.uint8)
    co, cs, us = native.scan_blocks(raw)
    live = us > 0
    return raw, co[live], cs[live], us[live]


def _counts(before) -> dict:
    return delta(before)["span_counts"]


@pytest.mark.parametrize("n_members", [3, 130])
def test_inflate_stage_records_every_sub_span(n_members):
    """One ``flate.inflate.wait`` per 128-lane group; pack, launch and
    verify per group besides the row build and the CRC pass; the stage
    span once."""
    block = 1024 if n_members == 3 else 16
    data = _payload(n_members * block - block // 2, 1)
    raw, co, cs, us = _members(data, block)
    assert len(co) == n_members
    groups = -(-n_members // 128)
    before = snapshot()
    out, _, dev = flate.inflate_blocks_device(
        raw, co, cs, us, return_device=True
    )
    got = _counts(before)
    assert out.tobytes() == data
    assert np.asarray(dev).tobytes() == data
    from hadoop_bam_tpu.utils.hbm import LEDGER

    LEDGER.release(dev)
    assert got["flate.stage.inflate_device"] == 1
    assert got["flate.inflate.launch"] == groups
    assert got["flate.inflate.wait"] == groups
    # Row build once, then the word transpose of each group.
    assert got["flate.inflate.pack"] == 1 + groups
    # Each group's unpack, the payload copy, the CRC pass.
    assert got["flate.inflate.verify"] == groups + 2
    # Each group's device view, their concatenation when there are
    # several, and the window build.
    assert got["flate.inflate.flatten"] == groups + (groups > 1) + 1
    assert not [k for k in got if k.startswith("flate.deflate.")]


@pytest.mark.parametrize("device_input", [False, True])
def test_deflate_stage_records_every_sub_span(device_input, monkeypatch):
    """Per group: the word build and the match kernel under ``.match``,
    the pack kernel under ``.emit``, and one fetch block under ``.wait``
    (the word tiles, their counts, the lengths and the ok column), plus
    the CRC column's fetch on the device-input path.  The pack counters
    count the match kernel's tokens and the rows the pack walks."""
    from hadoop_bam_tpu.ops.pallas import deflate_lanes as dl

    seen = []
    match = dl._deflate_match_kernel

    def recording_match(*a, **kw):
        out = match(*a, **kw)
        seen.append((np.asarray(out[1]), np.asarray(out[2])))
        return out

    monkeypatch.setattr(dl, "_deflate_match_kernel", recording_match)
    data = _payload(2500, 2)
    kw = dict(level=1, block_payload=1024, use_lanes=True)
    before = snapshot()
    if device_input:
        blob = flate.deflate_blocks_device(
            None, device_input=jnp.asarray(np.frombuffer(data, np.uint8)),
            **kw,
        )
    else:
        blob = flate.deflate_blocks_device(data, **kw)
    got = _counts(before)
    from hadoop_bam_tpu.spec import bgzf

    assert bgzf.decompress_all(blob + bgzf.TERMINATOR) == data
    assert flate.LAST_DEFLATE_STATS.lanes == 3
    assert got["flate.stage.deflate_device"] == 1
    # The word build and the match kernel of the one group.
    assert got["flate.deflate.match"] == 2
    # The pack kernel of the one group.
    assert got["flate.deflate.emit"] == 1
    assert got["flate.deflate.wait"] == 1 + device_input
    assert got.get("flate.deflate.crc", 0) == int(device_input)
    assert got["flate.deflate.frame"] == 1
    assert not [k for k in got if k.startswith("flate.inflate.")]
    ((cnts, ntok),) = seen
    counters = delta(before)["counters"]
    assert counters["flate.deflate.pack_tokens"] == int(ntok.sum()) > 0
    assert counters["flate.deflate.pack_waves"] == int(
        cnts[:: dl._CNT_ROWS].max(axis=1).sum()
    )


def test_deflate_wait_spans_hold_each_group_fetch():
    """Two groups: two match spans, one pack launch and one fetch block
    each; the framing stays one."""
    data = _payload(129 * 16, 3)
    before = snapshot()
    flate.deflate_blocks_device(
        data, level=1, block_payload=16, use_lanes=True
    )
    got = _counts(before)
    assert got["flate.deflate.match"] == 4
    assert got["flate.deflate.emit"] == 2
    assert got["flate.deflate.wait"] == 2
    assert got["flate.deflate.frame"] == 1


def _resident_batch(n=20, seed=4):
    rng = np.random.default_rng(seed)
    recs, offs, lens, p = [], [], [], 0
    for _ in range(n):
        body = rng.integers(0, 256, int(rng.integers(40, 90)), np.uint8)
        size = np.frombuffer(len(body).to_bytes(4, "little"), np.uint8)
        recs.append(np.concatenate([size, body]))
        offs.append(p + 4)
        lens.append(len(body))
        p += len(body) + 4
    data = np.concatenate(recs)
    return RecordBatch(
        soa={"rec_off": np.asarray(offs, np.int64),
             "rec_len": np.asarray(lens, np.int64)},
        data=data, keys=np.arange(n, dtype=np.int64),
        device_data=jnp.asarray(data),
    )


def test_encode_part_records_the_gather_span():
    """The device part write: the gather in ``bam.write.gather``, then
    the deflate stage with its CRC on the device-input path."""
    from hadoop_bam_tpu.device_stream import DeviceStream

    b = _resident_batch()
    order = np.random.default_rng(5).permutation(b.n_records)
    before = snapshot()
    blob = DeviceStream().encode_part(b, order, None, 1)
    got = _counts(before)
    assert blob is not None
    off, ln = b.soa["rec_off"], b.soa["rec_len"]
    want = b"".join(
        b.data[off[i] - 4 : off[i] + ln[i]].tobytes() for i in order
    )
    assert zlib.decompress(blob[18:], -15) == want
    assert got["bam.write.gather"] == 1
    assert got["flate.stage.deflate_device"] == 1
    assert got["flate.deflate.crc"] == 1
    assert delta(before)["counters"]["bam.device_write_parts"] == 1


def _jitted(mod):
    return {
        name: fn for name, fn in vars(mod).items()
        if callable(fn) and hasattr(fn, "lower")
        and hasattr(fn, "__wrapped__")
    }


def test_lane_kernels_have_distinct_names():
    """Device runs are named after the jitted function: every jitted
    function across ``ops/pallas`` has a name of its own, and none is
    the generic ``_launch`` the lane kernels once shared."""
    import importlib
    import pkgutil

    import hadoop_bam_tpu.ops.pallas as pallas

    names = {}  # program name -> the distinct functions jitted under it
    for info in pkgutil.iter_modules(pallas.__path__):
        mod = importlib.import_module(f"{pallas.__name__}.{info.name}")
        for fn in _jitted(mod).values():
            # A re-export, or a second jit of one function (a donating
            # variant), is the same program.
            names.setdefault(fn.__name__, set()).add(fn.__wrapped__)
    dup = sorted(k for k, v in names.items() if len(v) > 1)
    assert not dup, dup
    assert "_launch" not in names
    for kernel in ("_inflate_lanes_kernel", "_deflate_match_kernel",
                   "_deflate_pack_kernel", "_inflate_fixed_kernel", "_record_scan_kernel",
                   "_rans_lanes_kernel"):
        assert kernel in names, kernel


@pytest.mark.parametrize("name", INFLATE_SPANS + DEFLATE_SPANS + WRITE_SPANS)
def test_new_span_names_match_the_metric_pattern(name):
    assert re.match(METRIC_NAME_PATTERN, name)
