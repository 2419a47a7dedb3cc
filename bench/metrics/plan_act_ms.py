"""plan_act_ms: milliseconds per policy run outside ingest and match:
the ``run`` span minus its ``run.ingest`` and ``run.match`` spans, which
leaves the lexsort plan and ``run.act``."""
from bench.harness import mean, span_seconds


def read(rec):
    return mean([(span_seconds(r.spans, "run")
                  - span_seconds(r.spans, "run.ingest")
                  - span_seconds(r.spans, "run.match")) * 1e3
                 for r in rec.of("policy_run") if r.spans])
