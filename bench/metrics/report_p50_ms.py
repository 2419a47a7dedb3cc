"""report_p50_ms: median latency of the same queries as report_p95_ms:
what one tenant at a dashboard feels. Host clock."""
from bench.harness import percentile


def read(rec):
    lat = [(r.end - r.due) * 1e3 for r in rec.queries]
    return percentile(lat, 50)
