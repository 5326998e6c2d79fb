"""A run whose timed path is broken underneath reports ``correct`` false:
one case for each fault a sort cell can have.  Each control of the
reference fails the comparison too."""

import dataclasses
import time

import numpy as np
import pytest

from benchmark import harness, validate
from benchmark.references import coordinate_sort as reference
from helpers import answer_job, sorted_order

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CELLS = ["wgs_sort.bgzf6"]


def small(cell, records=4000):
    c = harness.resolve(cell)
    c.traffic = dict(c.traffic, records_per_job=records)
    return c


def unchanged(c, tr):
    """The step returns its state unchanged: input order."""
    return reference.expected(c, tr, np.arange(c.n_records))


def half_left_out(c, tr):
    w = reference.expected(c, tr)
    keep = len(w.lengths) // 2
    return dataclasses.replace(w, records=w.records[: int(w.lengths[:keep].sum())], lengths=w.lengths[:keep])


def exchange_left_out(c, tr):
    """Four pieces each sorted alone: the exchange or merge between them
    left out."""
    keys = reference.sort_keys(c.stream, c.offsets, c.lengths)
    q = np.array_split(np.arange(c.n_records), 4)
    return reference.expected(c, tr, np.concatenate([p[np.argsort(keys[p], kind="stable")] for p in q]))


def record_altered(c, tr):
    w = reference.expected(c, tr, sorted_order(c))
    b = bytearray(w.records)
    b[int(w.lengths[:10].sum()) + 100] ^= 0x01  # inside record 10's bases
    return dataclasses.replace(w, records=bytes(b))


def ties_reversed(c, tr):
    keys = reference.sort_keys(c.stream, c.offsets, c.lengths)
    return reference.expected(c, tr, np.lexsort((-np.arange(len(keys)), keys)))


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [unchanged, half_left_out, exchange_left_out, record_altered, ties_reversed])
def test_a_broken_path_is_not_correct(cell, fault):
    c = small(cell)
    r = harness.run(c, 2**31 + 99, 0.5, False, time.time(), device=CPU, job_fn=answer_job(c.traffic, fault))
    assert r["attempted"] >= 1
    assert r["correct"] is False
    assert r["failed"] == r["attempted"]
    assert r["checks"]["records_wrong"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_stored_output_is_not_correct(cell):
    """The right records written as stored members: the size check fails."""
    c = small(cell)
    r = harness.run(c, 2**31 + 98, 0.5, False, time.time(), device=CPU,
                    job_fn=answer_job(c.traffic, level=0))
    excess = r["checks"]["output_size_excess"]
    assert r["checks"]["records_wrong"]["value"] == 0
    assert r["correct"] is False and excess["value"] > excess["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_reference_answer_is_correct(cell):
    c = small(cell)
    r = harness.run(c, 2**31 + 99, 0.5, False, time.time(), device=CPU, job_fn=answer_job(c.traffic))
    assert r["correct"] is True and r["failed"] == 0
    assert list(r["checks"]) == ["framing_errors", "header_wrong", "records_wrong", "output_size_excess"]
    assert abs(r["checks"]["output_size_excess"]["value"]) < 1e-9
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_every_control_fails_a_limit(cell):
    """The readings a limit is set from: the program's answer within every
    limit, each control over one of them."""
    c = small(cell, 2000)
    rows = list(validate.readings(c, [5, 2**31 + 5], answer_job(c.traffic)))
    limits = c.traffic["limits"]
    sides = {r["side"] for r in rows}
    assert sides == {"program", *reference.CONTROLS}
    for r in rows:
        over = [k for k in limits if r[k] > limits[k]]
        assert (not over) if r["side"] == "program" else over, r
    s = validate.summary(rows)
    assert s["stored"]["output_size_excess"] > limits["output_size_excess"]
    assert s["ties_reversed"]["records_wrong"] > 0


def test_the_program_on_the_cpu_agrees_with_the_reference():
    """The real sort, on the host path the CPU takes, at a small size.  The
    cell's path rule names the chip's device tiers, which the CPU does not
    take, so the host path is the one this run expects."""
    c = small("wgs_sort.bgzf6", 3000)
    c.traffic = dict(c.traffic, expect={"backend": "single-device"})
    r = harness.run(c, 17, 5.0, False, time.time(), device=CPU)
    assert r["correct"] is True and r["attempted"] >= 1 and r["failed"] == 0
