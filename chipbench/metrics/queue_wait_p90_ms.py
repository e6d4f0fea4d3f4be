"""queue_wait_p90_ms: 90th percentile over the window's requests of
due -> submit (the client) plus submit -> admission (the engine's
``GenerationResult.queue_wait_s``)."""

from chipbench.stats import percentile


def read(run):
    return percentile([((g.submit - g.due) + g.queue_wait_s) * 1e3
                       for g in run.logs.values()
                       if g.queue_wait_s is not None], 90)
