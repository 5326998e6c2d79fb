"""The codec tiers' per-layer metrics on a chip trace recorded with the
sub-spans inside the codec stages (``record_trace.py``, one v5e):

    python3 -m benchmark.tests.record_trace benchmark/tests/data/chip_trace_codec.xplane.pb.gz
"""

import gzip
import os

import pytest

from benchmark import harness, trace

HERE = os.path.dirname(os.path.abspath(__file__))
DEVICE_METRICS = ("deflate_match_device_ms.sort", "deflate_emit_device_ms.sort",
                  "crc_device_ms.sort", "gather_device_ms.sort")
STAGES = ("flate.stage.inflate_device", "flate.stage.deflate_device")
PREFIXES = ("flate.inflate.", "flate.deflate.")


@pytest.fixture(scope="module")
def red():
    path = os.path.join(HERE, "data", "chip_trace_codec.xplane.pb.gz")
    if not os.path.exists(path):
        pytest.fail("the recorded codec chip trace is missing")
    return trace.reduce(trace.load_serialized(gzip.decompress(open(path, "rb").read())))


def test_device_metrics_read_the_sub_spans(red):
    cell = harness.resolve("wgs_sort.bgzf6")
    job = harness.Job(0.0, 1.0, "", report={"counters": {}, "span_seconds": {}})
    ctx = harness.Context([job], 2000, red, {}, {}, {})
    for name in DEVICE_METRICS:
        v = cell.reader(name)(ctx)
        assert v is not None and 0 < v <= 1000.0 * red.window_s, name


def test_deflate_parts_stay_inside_their_stage(red):
    parts = sum(red.device_s([s])[0] for s in (
        "flate.deflate.match", "flate.deflate.emit", "flate.deflate.crc"))
    assert 0 < parts <= red.device_s(["flate.stage.deflate_device"])[0] + 1e-9


def test_idle_gaps_go_to_the_sub_spans(red):
    """At most a tenth of the codec stages' idle time is left to the bare
    stage spans; the rest is charged to the host work or wait inside."""
    bare = sum(red.gaps.get(stage, 0) for stage in STAGES)
    inside = sum(ns for name, ns in red.gaps.items() if name.startswith(PREFIXES))
    assert inside > 0
    assert bare <= 0.1 * (bare + inside), red.gaps


def test_runs_are_named_after_their_kernels(red):
    names = [n for n, _ in red.top_runs()]
    assert any(n.endswith(":jit__inflate_lanes_kernel") for n in names), names
    assert any(n.endswith(":jit__deflate_match_kernel") for n in names), names
    assert not [n for n, _ in red.top_runs(k=1000) if n.endswith(":jit__launch")]
