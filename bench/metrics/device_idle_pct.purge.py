"""device_idle_pct.purge: the share of the traced window in which no
operation ran on the device, in percent, in the purge cell."""


def read(rec):
    if rec.trace is None or not rec.trace.ops:
        return None
    return 100.0 * (1.0 - rec.trace.busy_s() / rec.trace.window_s)
