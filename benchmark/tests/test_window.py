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
    """Jobs that fell back (here: every other job on the wrong backend) are
    failed, and their records leave the rate; the jobs on the path count,
    and the output of all of them is still right."""
    import time

    cell = fake_cell()
    inner = answer_job(cell.traffic, delays=[0.1] * 100,
                       backend=[cell.traffic["expect"]["backend"], "host-fallback"])
    jobs = []

    def job_fn(src, out, argv):
        jobs.append(inner(src, out, argv))
        return jobs[-1]

    r = harness.run(cell, 6, 1.0, False, time.time(), device=CPU, job_fn=job_fn)
    warm, window = jobs[0], jobs[1:]
    off = len(window[::2])  # the window's first job is the cycle's second
    assert r["attempted"] == len(window) >= 3
    assert r["correct"] is True
    assert r["failed"] == off
    # The window opens after the warm-up's end and before its first job.
    on_records = 2000 * (len(window) - off)
    rate = r["metrics"]["sort_records_per_s"]["value"]
    assert on_records / (window[-1].end - warm.end) <= rate <= on_records / (window[-1].end - window[0].start)


def test_every_job_of_the_window_off_the_path_gives_no_result():
    """A window in which no job took the cell's path gives no rate, not 0."""
    import time

    cell = fake_cell()
    job_fn = answer_job(cell.traffic, backend=[cell.traffic["expect"]["backend"]] + ["host-fallback"] * 99)
    with pytest.raises(harness.OffPath, match="every job of the window") as e:
        harness.run(cell, 6, 0.3, False, time.time(), device=CPU, job_fn=job_fn)
    assert job_fn.calls >= 2
    assert "backend 'host-fallback'" in str(e.value)


def test_a_warm_up_off_the_path_ends_the_run_in_set_up():
    import time

    cell = fake_cell()
    job_fn = answer_job(cell.traffic, backend="mesh[4]")
    with pytest.raises(harness.OffPath, match="warm-up") as e:
        harness.run(cell, 7, 0.3, False, time.time(), device=CPU, job_fn=job_fn)
    assert job_fn.calls == 1
    assert "backend 'mesh[4]', not 'device-parse'" in str(e.value)


def test_a_traced_job_off_the_path_gives_no_result():
    import time

    cell = fake_cell()
    job_fn = answer_job(cell.traffic, backend=[cell.traffic["expect"]["backend"], "host-fallback"])
    with pytest.raises(harness.OffPath, match="the traced job"):
        harness.run(cell, 8, 0.3, True, time.time(), device=CPU, job_fn=job_fn)
    assert job_fn.calls == 2


def test_a_job_that_raises_is_off_the_path():
    """A job function that raises in every job gives :class:`OffPath` with
    the error, not the bare exception."""
    import time

    def crash(src, out, argv):
        crash.calls += 1
        raise RuntimeError("the sort crashed")

    crash.calls = 0
    with pytest.raises(harness.OffPath, match="the sort crashed"):
        harness.run(fake_cell(), 9, 0.3, False, time.time(), device=CPU, job_fn=crash)
    assert crash.calls == 1


def test_a_run_off_the_path_exits_4_without_a_result(monkeypatch, capsys):
    from benchmark import run

    def off_path(*args, **kwargs):
        raise harness.OffPath("wgs_sort.bgzf6", "the warm-up job", ["backend 'mesh[4]', not 'device-parse'"])

    monkeypatch.setattr(run, "use_checkout_cache", lambda: None)
    monkeypatch.setattr(harness, "run", off_path)
    assert run.main(["--workload", "wgs_sort.bgzf6", "--seed", "1", "--seconds", "1"]) == 4
    out, err = capsys.readouterr()
    assert out == ""
    assert "cell wgs_sort.bgzf6: the warm-up job did not take the cell's path" in err
    assert "backend 'mesh[4]', not 'device-parse'" in err


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
