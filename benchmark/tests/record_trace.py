"""Records the small chip trace that ``test_window.py`` reduces.

    python3 -m benchmark.tests.record_trace <out.xplane.pb.gz>

Run on one chip: a 2,000-record ``wgs30x`` job at level 6 with every device
tier on, warmed up, then traced as a benchmark run traces its job.  The
trace is shrunk before it is written: only the host and TPU planes stay,
and each TPU's ``XLA Ops`` line, hundreds of thousands of kernel-internal
ops, becomes the union of their intervals, which is all the reduction reads
from it.
"""

import gzip
import os
import sys
import tempfile

from benchmark import harness, trace


def shrink(xspace: bytes) -> bytes:
    """The planes the reduction reads, with ``XLA Ops`` merged into busy
    intervals."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    src = xplane_pb2.XSpace()
    src.ParseFromString(xspace)
    keep = [p for p in src.planes if p.name == "/host:CPU" or trace._DEVICE_RE.match(p.name)]
    del src.planes[:]
    for p in keep:
        for line in p.lines:
            if line.name != "XLA Ops":
                continue
            base = line.timestamp_ns * 1000
            spans = trace._union([(base + e.offset_ps, base + e.offset_ps + e.duration_ps) for e in line.events])
            meta = max(p.event_metadata) + 1
            p.event_metadata[meta].id = meta
            p.event_metadata[meta].name = "busy"
            del line.events[:]
            for a, b in spans:
                e = line.events.add()
                e.metadata_id, e.offset_ps, e.duration_ps = meta, a - base, b - a
        src.planes.append(p)
    return src.SerializeToString()


def record(out: str) -> None:
    cell = harness.resolve("wgs_sort.bgzf6")
    harness.require_chips(1)
    gen = cell.generator()
    corpus = gen.generate(cell.config, 2000, 11)
    with tempfile.TemporaryDirectory() as d:
        src = os.path.join(d, "input")
        gen.write(corpus, src, int(cell.traffic["input_level"]))
        argv = list(cell.traffic["argv"])
        harness.run_job(src, os.path.join(d, "warm.out"), argv)
        job, xplane = harness.profile_job(harness.run_job, src, os.path.join(d, "out"), argv,
                                          os.path.join(d, "trace"))
        if job.error:
            raise SystemExit(job.error)
        with open(xplane, "rb") as f, open(out, "wb") as g:
            g.write(gzip.compress(shrink(f.read()), 9))


if __name__ == "__main__":
    record(sys.argv[1])
