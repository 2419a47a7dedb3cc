"""act_gather_ms: milliseconds per policy run in the ``run.act.gather``
spans: the catalog read of each planned chunk (``Catalog.column_batch``,
or ``get_batch`` on the Entry path), summed over the run's chunks."""
from bench.program_spans import span_ms


def read(rec):
    return span_ms(rec, "run.act.gather")
