"""ttft_p50_ms: median time to first token over every request due in
the window, from when it was due to when ``step()`` handed its first
token to the host."""

from chipbench.stats import percentile


def read(run):
    return percentile([(g.times[0] - g.due) * 1e3
                       for g in run.logs.values() if g.times], 50)
