"""ttft_p90_ms: the 90th percentile of the same samples as ttft_p50_ms
(a request that never gives a first token fails the run instead)."""

from chipbench.stats import percentile


def read(run):
    return percentile([(g.times[0] - g.due) * 1e3
                       for g in run.logs.values() if g.times], 90)
