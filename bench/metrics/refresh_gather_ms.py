"""refresh_gather_ms: milliseconds per policy run in the
``store.refresh.gather`` span: the catalog read of the dirty rows
(``Catalog.gather_rows``) before the store scatters them."""
from bench.program_spans import span_ms


def read(rec):
    return span_ms(rec, "store.refresh.gather")
