"""Runs one benchmark cell once and prints its result line.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The last line of standard output is the
result; the last lines of standard error are the numbers compared with the
reference, each beside its limit.  Without a result it exits

- 3 unless JAX finds a TPU with as many chips as the cell asks for;
- 4 where the program did not take the cell's path: the warm-up job (the
  run then ends in set-up, before the window opens), the traced job, or
  every job of the window raised, exited nonzero or broke a path rule.  The
  reasons are on standard error.  A program that cannot run a cell at all,
  such as a parent of the change that adds the cell, fails so after one job.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def use_checkout_cache() -> None:
    """Keeps JAX's compile cache at one fixed place inside the checkout, so
    that only a checkout's first run compiles; the program takes the same
    directory from ``JAX_COMPILATION_CACHE_DIR``."""
    cache = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    sys.path.insert(0, ROOT)
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # No eviction: an evicting cache keeps an access-time file per entry,
    # and a missing one fails every later write.
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    use_checkout_cache()
    from benchmark import harness

    cell = harness.resolve(args.workload, ROOT)
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_PROCESS)
    except harness.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    except harness.OffPath as e:
        print(f"benchmark: {e}\nbenchmark: no result, {time.time() - T_PROCESS:.3f} s after the process started",
              file=sys.stderr)
        return 4
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
