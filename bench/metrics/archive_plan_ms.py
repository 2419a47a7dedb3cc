"""archive_plan_ms: milliseconds per ``lhsm_archive`` run in the
``run.plan`` span (the lexsort of the match set by mtime and fid, and the
sorted plan's gathers), over the runs whose root span's ``policy``
attribute names that policy."""
from bench.program_spans import mean, span_seconds, spans_named

POLICY = "lhsm_archive"
SPAN = "run.plan"


def read(rec):
    trees = [r.spans for r in rec.of("policy_run") if r.spans
             and r.spans.get("attrs", {}).get("policy") == POLICY]
    if not any(spans_named(t, SPAN) for t in trees):
        return None
    return mean([span_seconds(t, SPAN) * 1e3 for t in trees])
