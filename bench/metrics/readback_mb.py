"""readback_mb: megabytes (1e6 B) per policy run copied device to host
for the match: the ``d2h_bytes`` of the ``store.match.readback`` spans
(mask, rule and aggregates)."""
from bench.program_spans import attr_per_run


def read(rec):
    got = attr_per_run(rec, "store.match.readback", "d2h_bytes")
    return None if got is None else got / 1e6
