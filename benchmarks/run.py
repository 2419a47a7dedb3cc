"""Benchmark harness: one module per paper table/claim.

Prints ``name,us_per_call,derived`` CSV rows (harness contract). Run:
    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--smoke]
                                            [--json OUT] [--trajectory DIR]

``--smoke`` shrinks problem sizes (CI budget: whole suite < 2 min);
``--json OUT`` additionally writes a BENCH_*.json-shaped dict for one run;
``--trajectory DIR`` *appends* each module's rows as a dated entry to
``DIR/BENCH_<module>.json`` (``bench_policy`` -> ``BENCH_policy.json``),
so numbers accumulate PR over PR and later PRs can diff against earlier
ones instead of starting an empty trajectory every time.
"""
from __future__ import annotations

import argparse
import datetime
import inspect
import json
import os
import sys
import time
import traceback

MODULES = [
    "bench_scan",        # Fig. 3: parallel DFS + multi-client scan
    "bench_changelog",   # SII-C2/SIII-A2: changelog rates, async dirty-tag
    "bench_stats",       # SII-B3: O(1) pre-aggregated reports
    "bench_policy",      # SII-B1: policy matching (4 evaluators + engine)
    "bench_find_du",     # SII-B4: find/du clones vs POSIX walk
    "bench_reports",     # PR6: mesh-resident reports vs host folds
    "bench_serving",     # PR7: multi-tenant scoped serving (perm bitmaps)
    "bench_tiering",     # PR8: out-of-core catalogs (warm-segment streaming)
    "bench_kvtier",      # adapted C7/C8: KV-page tiering + paged serving
    "bench_telemetry",   # PR9: registry/span overhead on warm hot paths
    "roofline_report",   # SRoofline summary rows from the dry-run artifacts
]


def _call_run(mod, smoke: bool) -> list:
    """Pass smoke= only to modules that accept it (older ones don't)."""
    sig = inspect.signature(mod.run)
    if "smoke" in sig.parameters:
        return mod.run(smoke=smoke)
    return mod.run()


def _append_trajectory(traj_dir: str, name: str, rows: list,
                       smoke: bool, elapsed_s: float,
                       short: str = None) -> str:
    """Append one dated entry to BENCH_<short>.json (atomic rewrite).

    ``short`` defaults to the module name minus its ``bench_`` prefix; a
    module may override it with a module-level ``TRAJECTORY`` attribute
    to append into another module's trajectory file (``bench_serving``
    extends ``BENCH_reports.json`` rather than starting a new table).
    """
    if short is None:
        short = name[len("bench_"):] if name.startswith("bench_") else name
    os.makedirs(traj_dir, exist_ok=True)
    path = os.path.join(traj_dir, f"BENCH_{short}.json")
    payload = {"suite": f"benchmarks.{name}", "entries": []}
    if os.path.exists(path):
        try:
            with open(path, encoding="utf-8") as f:
                loaded = json.load(f)
            if isinstance(loaded.get("entries"), list):
                payload = loaded
        except (OSError, ValueError):
            pass                     # corrupt trajectory: restart it
    payload["entries"].append({
        "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"),
        "smoke": bool(smoke),
        "elapsed_s": round(elapsed_s, 3),
        "rows": [{"name": n, "us_per_call": float(us),
                  "derived": str(derived)} for n, us, derived in rows],
    })
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2)
    os.replace(tmp, path)
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="shrink sizes for a <2 min CI run")
    ap.add_argument("--json", dest="json_out", default=None, metavar="OUT",
                    help="also write a BENCH_*.json-shaped result dict")
    ap.add_argument("--trajectory", default=None, metavar="DIR",
                    help="append each module's rows as a dated entry to "
                         "DIR/BENCH_<module>.json (perf trajectory over "
                         "PRs)")
    args = ap.parse_args()
    if args.only and args.only not in MODULES:
        ap.error(f"unknown module {args.only!r} (choose from {MODULES})")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = 0
    results = []
    t_start = time.time()
    for name in MODULES:
        if args.only and args.only != name:
            continue
        try:
            t_mod = time.time()
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            rows = _call_run(mod, args.smoke)
            for row in rows:
                n, us, derived = row
                print(f"{n},{us:.2f},{derived}", flush=True)
                results.append({"name": n, "us_per_call": float(us),
                                "derived": str(derived), "module": name})
            if args.trajectory:
                _append_trajectory(args.trajectory, name, rows,
                                   args.smoke, time.time() - t_mod,
                                   short=getattr(mod, "TRAJECTORY", None))
        except Exception as e:
            failed += 1
            print(f"{name},NaN,ERROR_{type(e).__name__}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if args.json_out:
        payload = {
            "suite": "benchmarks.run",
            "smoke": bool(args.smoke),
            "elapsed_s": round(time.time() - t_start, 3),
            "failed_modules": failed,
            "rows": results,
        }
        with open(args.json_out, "w", encoding="utf-8") as f:
            json.dump(payload, f, indent=2)
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
