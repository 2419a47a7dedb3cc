"""report_kernel_ms: device milliseconds per report query in the report
launches (the policy_scan match under find, column top-k, threshold rows,
range aggregate, scoped cube), from the profiler trace of the window."""
# the report launches' programs, as the trace names them
PROGRAMS = (r"^jit_mesh_(policy_scan_batch|column_topk|threshold_rows"
            r"|range_aggregate|scoped_cube)$")


def read(rec):
    queries = rec.queries
    if rec.trace is None or not queries \
            or not rec.trace.matching(PROGRAMS):
        return None
    return rec.trace.seconds(PROGRAMS) / len(queries) * 1e3
