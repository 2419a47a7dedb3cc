"""idle_unspanned_ms: device-idle milliseconds per policy run that no
layer of the program explains: idle time inside the harness's
``bench.policy_run`` annotations while the innermost open program span
is ``run`` itself or none (the harness's glue, the telemetry snapshots
around the run). From the profiler trace, with the program's ``rbh.``
annotations (``bench/program_spans.py``)."""
from bench import program_spans


def read(rec):
    runs = rec.of("policy_run")
    if rec.trace is None or not rec.trace.ops or not runs:
        return None
    spans = program_spans.host_spans(rec)
    if not any(name == program_spans.PREFIX + "run"
               for name, _, _ in spans):
        return None
    idle = program_spans.idle_by_span(rec.trace, spans)
    unspanned = idle.get(program_spans.PREFIX + "run", 0.0) \
        + idle.get(program_spans.UNSPANNED, 0.0)
    return unspanned / len(runs) * 1e3
