"""The readings a cell's limits are set from, many seeds in one process.

    python3 -m benchmark.validate --workload <cell> --seeds 1,2,3 [--controls a,b]

For each seed: the cell's input, one job through the timed path (the
program's command line, at the cell's size), and the numbers the benchmark
compares.  Then each control of the cell's reference on the same input: an
answer with one guarantee broken, compared the same way (``--controls``
names the ones to run; all by default).  One JSON line
per seed and side; the last line gives, per number, the largest reading of
the program and the smallest of each control.  Needs the cell's chips, as
a run does; the benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Iterator, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell, seeds: List[int], job_fn=None, controls: Optional[List[str]] = None) -> Iterator[dict]:
    """One row for the program and one for each control, per seed."""
    from benchmark import harness

    job_fn = job_fn or harness.run_job
    tr = cell.traffic
    gen, ref = cell.generator(), cell.reference()
    controls = list(ref.CONTROLS) if controls is None else controls
    work = tempfile.mkdtemp(prefix="validate-")
    try:
        for seed in seeds:
            corpus = gen.generate(cell.config, int(tr["records_per_job"]), seed)
            src = os.path.join(work, "input")
            gen.write(corpus, src, int(tr["input_level"]))
            job = harness.call_job(job_fn, src, os.path.join(work, "out"), list(tr["argv"]))
            want = ref.expected(corpus, tr)
            data = b""
            if job.error:
                print(job.error, file=sys.stderr)
            else:
                with open(job.out, "rb") as f:
                    data = f.read()
            rules = harness.path_breaks(job, tr.get("expect", {}))
            yield {"side": "program", "seed": seed, "wall_s": job.wall, **ref.compare(data, want),
                   "path_rule": rules}
            for name in controls:
                yield {"side": name, "seed": seed, **ref.compare(ref.CONTROLS[name](corpus, want, tr), want)}
    finally:
        shutil.rmtree(work, ignore_errors=True)


def summary(rows: List[dict]) -> dict:
    """Per side and number: the program's largest reading, each control's
    smallest."""
    out: dict = {}
    for r in rows:
        pick = max if r["side"] == "program" else min
        side = out.setdefault(r["side"], {})
        for k, v in r.items():
            if k not in ("side", "seed", "wall_s", "path_rule"):
                side[k] = pick(side.get(k, v), v)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default=None, help="comma-separated; all by default")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark.run import use_checkout_cache

    use_checkout_cache()
    from benchmark import harness

    cell = harness.resolve(args.workload, ROOT)
    try:
        harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"validate: {e}", file=sys.stderr)
        return 3
    rows = []
    t0 = time.time()
    controls = args.controls.split(",") if args.controls else None
    for row in readings(cell, [int(s) for s in args.seeds.split(",")], controls=controls):
        rows.append(row)
        print(json.dumps({**row, "t_s": round(time.time() - t0, 1)}), flush=True)
    print(json.dumps(summary(rows)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
