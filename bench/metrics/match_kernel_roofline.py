"""match_kernel_roofline: the policy_scan kernel's share of its roofline,
in percent. Bytes bound the match (compares and sums), so the least time
is the bytes the cell's data and policy need (``bench/roofline.py``:
referenced columns at their narrowest exact width, plus 5 B per match)
over the chip's HBM bandwidth (``bench/peaks.json``), divided by the
kernel's device time in the trace."""
# the Pallas call in the store's match program, as the trace names them
PROGRAM = r"^jit_mesh_policy_scan_batch$"
KERNEL = r'custom_call_target="tpu_custom_call"'


def read(rec):
    if rec.trace is None or rec.peaks is None or not rec.policy_bytes \
            or not rec.trace.matching(PROGRAM, KERNEL):
        return None
    secs = rec.trace.seconds(PROGRAM, KERNEL)
    least = sum(rec.policy_bytes) / (rec.peaks["hbm_bytes_per_s"]
                                     * rec.trace.n_devices)
    return 100.0 * least / secs
