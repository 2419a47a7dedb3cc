"""report_queue_ms: mean milliseconds a report query waited in the
serving queue: from when it was due to when its call started."""
from bench.harness import mean


def read(rec):
    return mean([(r.start - r.due) * 1e3 for r in rec.queries])
