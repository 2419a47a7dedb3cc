#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest offered rate whose backlog
does not grow over the window. Run once, on the chip, when the cell's
rate is chosen; the benchmark's own runs never run this.

    python3 bench/sweep.py --workload project_reports.scoped_steady \
        --seed 5 --rates 2,4,6,8 --seconds 30

One set-up, then one window per rate, each its own schedule. One JSON
line per rate on stdout: queries served, mean service and queue time,
p50 and p95 latency, and the queue wait of the window's first and last
thirds (a backlog that grows shows as a last third far above the first).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import numpy as np
    from bench import harness
    from bench.generator import Traffic
    cell = harness.prepare(args.workload, args.seed)
    if cell.tcfg["loop"] != "open":
        raise SystemExit("a sweep needs an open-loop cell")
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        tcfg = dict(cell.tcfg, rate_per_s=rate)
        traffic = Traffic(tcfg, cell.cfg, cell.dep.subjects,
                          cell.st0.n, args.seed + 1 + i)
        events = traffic.schedule(args.seconds)
        recs, window_s = harness.open_window(
            cell.dep, events, args.seconds, tcfg["drain_s"], [])
        ok = [r for r in recs if r.error is None]
        lat = np.array([(r.end - r.due) * 1e3 for r in ok])
        wait = np.array([(r.start - r.due) * 1e3 for r in ok])
        third = max(1, len(ok) // 3)
        print(json.dumps({
            "rate_per_s": rate, "queries": len(recs),
            "failed": len(recs) - len(ok), "window_s": window_s,
            "service_ms": float(np.mean([(r.end - r.start) * 1e3
                                         for r in ok])),
            "queue_ms": float(wait.mean()),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "queue_first_third_ms": float(wait[:third].mean()),
            "queue_last_third_ms": float(wait[-third:].mean()),
            "by_op_service_ms": {
                op: float(np.mean([(r.end - r.start) * 1e3 for r in ok
                                   if r.op == op]))
                for op in sorted({r.op for r in ok})}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
