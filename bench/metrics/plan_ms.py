"""plan_ms: milliseconds per policy run in the ``run.plan`` span: the
lexsort of the matches and the gathers of the sorted plan."""
from bench.program_spans import span_ms


def read(rec):
    return span_ms(rec, "run.plan")
