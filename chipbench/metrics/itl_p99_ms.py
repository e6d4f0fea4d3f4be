"""itl_p99_ms: 99th percentile of every gap between consecutive tokens
of every request, each token timed when ``step()`` returned it to the
host (so tokens of one decode block share a time, and a prefill that
holds a block back shows as one long gap)."""

from chipbench.stats import percentile


def read(run):
    gaps = []
    for g in run.logs.values():
        t = g.times
        gaps.extend((b - a) * 1e3 for a, b in zip(t, t[1:]))
    return percentile(gaps, 99)
