"""prefix_hit_share: prompt tokens whose KV pages came from the prefix
cache (the engine's ``prefix_hit_tokens``) over the prompt tokens it
prefilled (``prefill_tokens``), over the window, in %: the share of
prefill that starting at the first uncached page would remove.  None
where the program keeps no such counter."""


def read(run):
    hits = getattr(run.stats, "prefix_hit_tokens", None)
    if hits is None or not run.stats.prefill_tokens:
        return None
    return 100.0 * hits / run.stats.prefill_tokens
