"""Whole-job accounting, the failure rule, and the reduction of a trace
recorded on the chip."""

import gzip
import os

import pytest

from benchmark import harness, trace
from helpers import answer_job

HERE = os.path.dirname(os.path.abspath(__file__))


def fake_cell(records=2000):
    c = harness.resolve("wgs_sort.bgzf6")
    c.traffic = dict(c.traffic, records_per_job=records)
    return c


CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def test_the_job_open_at_the_window_end_counts_whole():
    """A job started inside the window runs to its end and counts with all
    its time: a stall at the end slows the rate."""
    import time

    cell = fake_cell()
    t = time.time()
    # Warm-up 0.3 s; jobs of 0.3, 0.3 and then a 2 s one that crosses the
    # 1.5 s window's end; no job starts after it.
    r = harness.run(cell, 5, 1.5, False, t, device=CPU,
                    job_fn=answer_job(cell.traffic, delays=[0.3, 0.3, 0.3, 2.0, 0.3]))
    assert r["attempted"] == 3
    rate = r["metrics"]["sort_records_per_s"]["value"]
    # Three jobs of 2000 records over at least 2.6 s.
    assert rate <= 3 * 2000 / 2.6
    assert r["correct"] and r["failed"] == 0


def test_a_job_off_the_path_adds_no_records():
    """A job that fell back (here: the wrong backend) is failed, and its
    records leave the rate; its output is still right."""
    import time

    cell = fake_cell()
    r = harness.run(cell, 6, 0.3, False, time.time(), device=CPU,
                    job_fn=answer_job(cell.traffic, backend="host-fallback"))
    assert r["correct"] is True
    assert r["failed"] == r["attempted"] >= 1
    assert r["metrics"]["sort_records_per_s"]["value"] == 0


def test_failure_rule():
    expect = {"backend": "device-parse", "nonzero": ["device_stream.decodes"], "shuffle_devices": 4}
    good = {"counters": {"device_stream.decodes": 2, "mh.shuffle.devices": 4},
            "run_manifest": {"backend": "device-parse", "degraded": False}}
    assert harness.failure_reasons(good, expect) == []
    moved = {**good, "counters": {**good["counters"], "flate.lanes_tierdown": 1}}
    assert harness.failure_reasons(moved, expect)
    moved = {**good, "counters": {**good["counters"], "bam.device_write_tierdown.size": 3}}
    assert harness.failure_reasons(moved, expect)
    degraded = {**good, "run_manifest": {"backend": "device-parse", "degraded": True, "reasons": ["x"]}}
    assert harness.failure_reasons(degraded, expect)
    host = {**good, "run_manifest": {"backend": "host-fallback", "degraded": False}}
    assert harness.failure_reasons(host, expect)
    three = {**good, "counters": {**good["counters"], "mh.shuffle.devices": 3}}
    assert harness.failure_reasons(three, expect)


def test_no_chip_is_an_error():
    with pytest.raises(harness.NoChip):
        harness.require_chips(1)


def test_reduction_of_a_chip_trace():
    path = os.path.join(HERE, "data", "chip_trace.xplane.pb.gz")
    if not os.path.exists(path):
        pytest.fail("the recorded chip trace is missing")
    red = trace.reduce(trace.load_serialized(gzip.decompress(open(path, "rb").read())))
    assert red.devices == [0]
    assert 0 < red.busy_s() <= red.window_s
    stages = {s: red.device_s([s])[0] for s in (
        "pipeline.stage.device_parse", "flate.stage.inflate_device", "flate.stage.deflate_device")}
    assert all(0 < t <= red.window_s for t in stages.values())
    # Device time of a stage is its programs' runs; together they stay
    # inside the busy time.
    assert sum(stages.values()) <= red.busy_s() + 1e-9
    assert red.device_s(["sort_bam.shuffle_sort"])[0] == 0
    names = [n for n, _ in red.top_runs()]
    assert names[0] == "flate.stage.inflate_device:jit__launch"
    assert "flate.stage.deflate_device:jit__emit_tokens_fixed" in names
    assert all(s > 0 for _, s in red.top_runs())
    assert sum(s for _, s in red.top_gaps()) == pytest.approx(red.window_s - red.busy_s(), rel=1e-6)
