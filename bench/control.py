#!/usr/bin/env python3
"""The control of a cell's comparison, read on the chip at the cell's size.

    python3 bench/control.py --workload scratch_purge.churn1 \
        --seeds 11,12,13 --seconds 10

For each seed, in one process: one run of the cell, then the numbers it
compares read twice on the same sampled answers, once for the program
(the lower reading) and once for the plain reference at bfloat16 put in
the program's place (the control, which must fail a limit: its
``correct``, judged by the harness's own rule, reads false). One JSON
line per seed on stdout. The benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse          # noqa: E402
import gc                # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--precision", default="bf16")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    t = T_START
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = harness.run_cell(args.workload, seed, args.seconds, False,
                               t_start=t, controls=(args.precision,))
        print(json.dumps({"seed": seed, "correct": out.result["correct"],
                          "program": {k: c["value"] for k, c in
                                      out.checks.items()},
                          "control": out.controls[args.precision],
                          "limits": {k: c["limit"] for k, c in
                                     out.checks.items()}}), flush=True)
        out = None
        gc.collect()
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
