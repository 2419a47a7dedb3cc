#!/usr/bin/env python3
"""The benchmark command: one run of one cell.

    python3 bench/run.py --workload scratch_purge.churn1 --seed 7 \
        --seconds 30 --trace 0

Runs on the machine it is started on and needs the chips the cell asks
for: it exits non-zero, printing no result, when JAX's first device is
not a TPU or there are too few. The last stdout line is the result, one
JSON object; the numbers compared with the reference are the last lines
on stderr. ``--trace 1`` profiles the window and reports the cell's
per-layer metrics instead of its end-to-end ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse          # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the compile cache lives at a fixed path inside the checkout: the
    # path is part of the cache key, and JAX reads it when it is imported
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    try:
        outcome = harness.run_cell(args.workload, args.seed, args.seconds,
                                   bool(args.trace), t_start=T_START)
    except harness.NoDevice as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 2
    harness.report(outcome)
    return 0


if __name__ == "__main__":
    sys.exit(main())
