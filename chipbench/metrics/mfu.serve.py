"""mfu.serve: model operations of the tokens the steps computed in the
traced window (prompt tokens from the program's prefill spans, generated
tokens as received) over the device's busy time in that window times
the chips' bf16 peak, in %.  A step that skips work (a cached prefix)
lowers the count with the time, so this cannot pass 100%."""

from chipbench import flops, trace


def read(run):
    if run.trace is None:
        return None
    f = flops.served_flops(run, run.t0, run.t_end)
    w0, w1 = run.trace_window
    busy = trace.busy_s(run.trace, w0, w1)
    if not f or busy <= 0:
        return None
    return 100.0 * f / (busy * run.chips * run.peak["bf16_flops_per_s"])
