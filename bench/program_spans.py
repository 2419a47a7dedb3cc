#!/usr/bin/env python3
"""The program's own spans, in its run trees and in the profiler trace.

Each span the program opens (``core/telemetry.py``) lands in the run's
span tree (``RunReport.telemetry["spans"]``, kept on each ``OpRecord``)
and also enters a profiler annotation named ``rbh.<span>``, so the
``--trace 1`` run's trace holds them on the device ops' clock. The
readers of ``bench/metrics/`` walk the trees with ``span_ms`` and
``attr_per_run``; ``host_spans`` reads the annotations back from the
trace file, and ``idle_by_span`` puts the device's idle time inside the
policy runs down to the innermost program span open at each instant.

    python3 bench/program_spans.py [trace dir]

prints, per policy run of the newest trace under the directory (by
default the purge cell's), each span's total and self milliseconds and
the device's idle milliseconds by innermost span.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench.harness import TRACE_DIR, mean, span_seconds  # noqa: E402

PREFIX = "rbh."                      # the program's annotation prefix
RUN = "bench.policy_run"             # the harness's annotation of a run
UNSPANNED = "none"                   # no program span open

HostSpan = Tuple[str, float, float]  # (annotation, start_ns, dur_ns)


# -- span trees ---------------------------------------------------------------

def spans_named(tree: Optional[dict], name: str) -> List[dict]:
    """Every span called ``name`` in a span tree, depth first."""
    if not tree:
        return []
    own = [tree] if tree["name"] == name else []
    return own + [s for c in tree.get("children", [])
                  for s in spans_named(c, name)]


def _trees(rec) -> List[dict]:
    return [r.spans for r in rec.of("policy_run") if r.spans]


def span_ms(rec, name: str) -> Optional[float]:
    """Milliseconds per policy run in the spans called ``name``; None
    where no run has one (a program without that span)."""
    trees = _trees(rec)
    if not any(spans_named(t, name) for t in trees):
        return None
    return mean([span_seconds(t, name) * 1e3 for t in trees])


def attr_per_run(rec, name: str, attr: str) -> Optional[float]:
    """The attribute ``attr`` of the spans called ``name``, summed within
    each policy run and averaged over the runs; None where no such span
    carries it."""
    trees = _trees(rec)
    per = [[s["attrs"][attr] for s in spans_named(t, name)
            if attr in s.get("attrs", {})] for t in trees]
    if not any(per):
        return None
    return mean([float(sum(v)) for v in per])


# -- the trace ------------------------------------------------------------------

def _newest(trace_dir: str) -> Optional[str]:
    """The newest trace file under a ``--trace 1`` run's directory."""
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def read_spans(path: str) -> List[HostSpan]:
    """The program's annotations on the host planes of one trace file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return [(ev.name, ev.start_ns, ev.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(PREFIX)]


def host_spans(rec) -> List[HostSpan]:
    """The program's annotations in the trace of this run's window (none
    where it was not traced)."""
    path = _newest(str(TRACE_DIR / rec.workload)) \
        if rec.trace is not None else None
    return read_spans(path) if path else []


def segments(spans: Sequence[HostSpan]) -> List[Tuple[float, float, str]]:
    """``(start_ns, end_ns, name)``: the time the spans cover, cut where
    the innermost open span changes. Spans nest on each thread; across
    threads the shortest open span counts as the innermost."""
    # at one instant ends sort before starts; a span of no length covers
    # nothing
    marks = sorted(m for i, (_, s, d) in enumerate(spans) if d > 0
                   for m in ((s + d, 0, i), (s, 1, i)))
    out: List[Tuple[float, float, str]] = []
    active: set = set()
    prev = None
    for t, opens, i in marks:
        if active and t > prev:
            inner = min(active, key=lambda k: spans[k][2])
            out.append((prev, t, spans[inner][0]))
        if opens:
            active.add(i)
        else:
            active.discard(i)
        prev = t
    return out


def _idle_gaps(summary) -> Dict[int, List[Tuple[float, float]]]:
    """Per device, the intervals between its operations."""
    per: Dict[int, list] = {}
    for e in summary.ops:
        per.setdefault(e.device, []).append((e.start_ns, e.end_ns))
    gaps: Dict[int, list] = {}
    for dev, ivs in per.items():
        ivs.sort()
        end = ivs[0][1]
        gaps[dev] = []
        for s, e in ivs[1:]:
            if s > end:
                gaps[dev].append((end, s))
            end = max(end, e)
    return gaps


def _runs(summary) -> List[Tuple[float, float]]:
    """The policy runs' ``(start_ns, end_ns)``, from the harness's
    annotations."""
    return sorted((s, s + d) for n, s, d in summary.host if n == RUN)


def seconds_in_runs(summary, program: str) -> float:
    """Device seconds of the programs matching ``program`` inside the
    policy runs, an operation cut to the run it overlaps, averaged over
    the devices."""
    runs = _runs(summary)
    ns = sum(max(0.0, min(e.end_ns, r1) - max(e.start_ns, r0))
             for e in summary.matching(program) for r0, r1 in runs)
    return ns / 1e9 / max(1, summary.n_devices)


def idle_by_span(summary, spans: Sequence[HostSpan]) -> Dict[str, float]:
    """Idle device seconds inside the policy runs (the harness's
    ``bench.policy_run`` annotations), each instant put down to the
    innermost program span open then (``none`` where none is), averaged
    over the devices. A gap is split where the span changes, so a gap
    that spans the whole host part of a run is shared among its
    layers, not given to the span at its middle."""
    runs = _runs(summary)
    segs = segments(spans)
    seg_starts = [s for s, _, _ in segs]
    gaps = _idle_gaps(summary)
    tot: Dict[str, float] = {}
    for dev_gaps in gaps.values():
        for g0, g1 in dev_gaps:
            for r0, r1 in runs:
                a, b = max(g0, r0), min(g1, r1)
                if a >= b:
                    continue
                covered = 0.0
                i = max(0, bisect.bisect_right(seg_starts, a) - 1)
                while i < len(segs) and segs[i][0] < b:
                    s, e, name = segs[i]
                    part = min(b, e) - max(a, s)
                    if part > 0:
                        tot[name] = tot.get(name, 0.0) + part / 1e9
                        covered += part
                    i += 1
                if b - a > covered:
                    tot[UNSPANNED] = tot.get(UNSPANNED, 0.0) \
                        + (b - a - covered) / 1e9
    scale = 1.0 / max(1, len(gaps))
    return {k: v * scale for k, v in tot.items()}


# -- the command ------------------------------------------------------------------

def breakdown(trace_dir: str) -> dict:
    """Per policy run: each span's total and self milliseconds and the
    device's idle milliseconds by innermost span."""
    from bench.tracing import reduce_trace
    path = _newest(trace_dir)
    if path is None:
        raise SystemExit(f"no trace under {trace_dir}")
    summary = reduce_trace(path, 0.0)
    spans = read_spans(path)
    runs = sum(1 for n, _, _ in summary.host if n == RUN)
    if not runs:
        raise SystemExit(f"no {RUN} annotation in {path}")
    per_run = 1e3 / runs
    total: Dict[str, float] = {}
    count: Dict[str, int] = {}
    for name, _, dur in spans:
        total[name] = total.get(name, 0.0) + dur / 1e9
        count[name] = count.get(name, 0) + 1
    own: Dict[str, float] = {}
    for s, e, name in segments(spans):
        own[name] = own.get(name, 0.0) + (e - s) / 1e9
    idle = idle_by_span(summary, spans)
    return {
        "trace": path, "policy_runs": runs,
        "spans_per_run": sum(count.values()) / runs,
        "span_ms": {n: {"count_per_run": count[n] / runs,
                        "total": total[n] * per_run,
                        "self": own.get(n, 0.0) * per_run}
                    for n in sorted(total, key=lambda k: -total[k])},
        "idle_ms": {n: v * per_run for n, v in
                    sorted(idle.items(), key=lambda kv: -kv[1])},
    }


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    trace_dir = argv[0] if argv else str(TRACE_DIR / "scratch_purge.churn1")
    print(json.dumps(breakdown(trace_dir), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
