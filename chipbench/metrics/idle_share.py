"""idle_share.serve and idle_share.batch (one name per kind of cell,
one reader): share of the traced window in which no operation ran on
the device (1 - union of device-op intervals / window), in %."""

from chipbench import trace


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    w0, w1 = run.trace_window
    return 100.0 * (1.0 - trace.busy_s(run.trace, w0, w1) / ((w1 - w0) * 1e-9))
