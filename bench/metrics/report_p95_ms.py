"""report_p95_ms: 95th percentile of the latency of every report query
due in the window, each timed from when it was due to when its answer
returned (queue wait included). Host clock."""
from bench.harness import percentile


def read(rec):
    lat = [(r.end - r.due) * 1e3 for r in rec.queries]
    return percentile(lat, 95)
