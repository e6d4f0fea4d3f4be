"""mfu.batch: model operations of every prompt and output token computed
in the window (as ``output_tok_s`` bounds it) over the window's seconds
times the chips' bf16 peak, in %."""

from chipbench import flops


def read(run):
    f = flops.served_flops(run, run.t0, run.t_end)
    if not f:
        return None
    return 100.0 * f / ((run.t_end - run.t0) * run.chips
                        * run.peak["bf16_flops_per_s"])
