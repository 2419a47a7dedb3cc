"""combine_ms: milliseconds per policy run in the ``store.match.combine``
span: the wait for the device plus the device_get of mask, rule and
aggregates."""
from bench.harness import mean, span_seconds


def read(rec):
    return mean([span_seconds(r.spans, "store.match.combine") * 1e3
                 for r in rec.of("policy_run") if r.spans])
