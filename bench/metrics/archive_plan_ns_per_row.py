"""archive_plan_ns_per_row: nanoseconds of ``run.plan`` per row planned
in ``lhsm_archive`` runs: the span's summed time over its summed ``rows``
attribute, over the runs whose root span's ``policy`` attribute names
that policy. A sort on the device or a merge of sorted runs lowers it;
the size of the match set alone does not. None where no such span
carries ``rows``."""
from bench.program_spans import spans_named

POLICY = "lhsm_archive"
SPAN = "run.plan"


def read(rec):
    plans = [s for r in rec.of("policy_run") if r.spans
             and r.spans.get("attrs", {}).get("policy") == POLICY
             for s in spans_named(r.spans, SPAN)
             if "rows" in s.get("attrs", {})]
    rows = sum(s["attrs"]["rows"] for s in plans)
    if not rows:
        return None
    return sum(s["elapsed_s"] for s in plans) * 1e9 / rows
