"""refresh_device_ms: device milliseconds per policy run in the store's
refresh programs (the dirty-row scatter, the block pad, the single-row
scatter and the cube scatter-add), from the profiler trace of the
window, cut to the harness's ``bench.policy_run`` annotations so a store
program that runs outside a run is not charged to it."""
from bench import program_spans

# the store's jitted programs, as the trace names them
PROGRAMS = r"^jit_store_(scatter_rows|scatter_row|pad_block|cube_scatter)$"


def read(rec):
    runs = rec.of("policy_run")
    if rec.trace is None or not runs or not rec.trace.matching(PROGRAMS):
        return None
    return program_spans.seconds_in_runs(rec.trace, PROGRAMS) \
        / len(runs) * 1e3
