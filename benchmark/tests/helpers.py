"""Stand-ins for the program's sort in the self-tests: they write an answer
made by the reference, as it is or broken on purpose."""

import itertools
import time

import numpy as np

from benchmark import bgzf, harness
from benchmark.corpus.aligned_pe import Corpus
from benchmark.references import coordinate_sort as reference


def corpus_from_bam(path: str) -> Corpus:
    payload = bgzf.read_bgzf(open(path, "rb").read()).payload
    lt = int.from_bytes(payload[4:8], "little")
    text = payload[8:8 + lt].decode()
    p = 8 + lt
    refs = []
    for _ in range(int.from_bytes(payload[p:p + 4], "little")):
        ln = int.from_bytes(payload[p + 4:p + 8], "little")
        name = payload[p + 8:p + 8 + ln - 1].decode()
        refs.append((name, int.from_bytes(payload[p + 8 + ln:p + 12 + ln], "little")))
        p += 8 + ln
    p += 4
    body = np.frombuffer(payload[p:], np.uint8)
    lengths = np.array([len(r) for r in reference._split_records(payload[p:])], np.int64)
    offsets = np.concatenate([[0], np.cumsum(lengths)[:-1]]).astype(np.int64)
    return Corpus(text, refs, body, offsets, lengths)


def sorted_order(c: Corpus) -> np.ndarray:
    return np.argsort(reference.sort_keys(c.stream, c.offsets, c.lengths), kind="stable")


def answer_job(traffic, make=None, delays=None, backend=None, level=None):
    """A job function: ``make(corpus, traffic)`` gives the ``Expected`` to
    write (default: the reference's own answer), at ``level`` (default: the
    traffic's output level); ``delays`` are slept in turn.  Its report is
    that of a job on the cell's path, on ``backend`` if given: a name, or a
    list of names taken job by job, over and over.  ``job.calls`` counts the
    jobs."""
    it = iter(delays or [])
    level = traffic["output_level"] if level is None else level
    expect = traffic.get("expect", {})
    backends = itertools.cycle(backend if isinstance(backend, list) else [backend or expect.get("backend")])
    counters = {"mh.shuffle.devices": 4, **{k: 1 for k in expect.get("nonzero", [])}}

    def job(src, out, argv):
        job.calls += 1
        t0 = time.perf_counter()
        time.sleep(next(it, 0.0))
        c = corpus_from_bam(src)
        want = make(c, traffic) if make else reference.expected(c, traffic)
        with open(out, "wb") as f:
            f.write(want.to_bam(level))
        report = {"counters": counters, "span_seconds": {},
                  "run_manifest": {"backend": next(backends), "degraded": False}}
        return harness.Job(t0, time.perf_counter(), out, report=report)

    job.calls = 0
    return job
