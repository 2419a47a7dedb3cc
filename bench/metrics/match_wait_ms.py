"""match_wait_ms: milliseconds per policy run in the ``store.match.wait``
spans: the host waiting for the match's results to be ready on the
device, before it copies them."""
from bench.program_spans import span_ms


def read(rec):
    return span_ms(rec, "store.match.wait")
