"""refresh_ms: host milliseconds per policy run in the store's refresh
(the ``store.refresh`` spans of the run's span tree). The span does not
wait for the device, so this is the host side of the scatter."""
from bench.harness import mean, span_seconds


def read(rec):
    return mean([span_seconds(r.spans, "store.refresh") * 1e3
                 for r in rec.of("policy_run") if r.spans])
