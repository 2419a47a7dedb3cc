"""archive_act_gather_ms: milliseconds per ``lhsm_archive`` run in the
``run.act.gather`` spans (the catalog read of each planned chunk), summed
over the run's chunks, over the runs whose root span's ``policy``
attribute names that policy."""
from bench.program_spans import mean, span_seconds, spans_named

POLICY = "lhsm_archive"
SPAN = "run.act.gather"


def read(rec):
    trees = [r.spans for r in rec.of("policy_run") if r.spans
             and r.spans.get("attrs", {}).get("policy") == POLICY]
    if not any(spans_named(t, SPAN) for t in trees):
        return None
    return mean([span_seconds(t, SPAN) * 1e3 for t in trees])
