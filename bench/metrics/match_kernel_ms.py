"""match_kernel_ms: device milliseconds per policy run in the policy_scan
Pallas kernel, from the profiler trace of the window."""
# the Pallas call in the store's match program, as the trace names them
PROGRAM = r"^jit_mesh_policy_scan_batch$"
KERNEL = r'custom_call_target="tpu_custom_call"'


def read(rec):
    runs = rec.of("policy_run")
    if rec.trace is None or not runs \
            or not rec.trace.matching(PROGRAM, KERNEL):
        return None
    return rec.trace.seconds(PROGRAM, KERNEL) / len(runs) * 1e3
