"""report_service_ms: mean milliseconds from the start of a report call
(Reports or ProfileCube) to its answer, the queue wait left out."""
from bench.harness import mean


def read(rec):
    return mean([(r.end - r.start) * 1e3 for r in rec.queries])
