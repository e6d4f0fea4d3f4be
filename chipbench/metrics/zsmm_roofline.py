"""zsmm_roofline.serve and zsmm_roofline.batch (one name per kind of
cell, one reader): over every ``zero_stall_matmul*`` event of the traced
window, the chip's least time (the larger of 2MNK over peak FLOP/s and
the call's least bytes over peak bandwidth, from the problem the
program recorded) over the device time spent, in %."""

from chipbench import trace


def read(run):
    if run.trace is None:
        return None
    w0, w1 = run.trace_window
    events = trace.kernel_events(run.trace, "zero_stall_matmul", w0, w1)
    got = trace.roofline(events, run.matmul_records, run.peak)
    if got is None:
        return None
    share, bound = got
    run.note(f"zsmm_roofline: {len(events)} calls; least time bound by "
             + ", ".join(f"{k} {v:.6f} s" for k, v in sorted(bound.items())))
    return 100.0 * share
