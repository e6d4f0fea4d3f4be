"""decode_slot_yield: decode tokens emitted over the rows the decode
blocks computed (dispatches x slots x steps per dispatch), from the
engine's counters over the window, in %."""


def read(run):
    s = run.stats
    rows = s.dispatches * run.num_slots * run.steps_per_dispatch
    return 100.0 * s.decode_tokens / rows if rows else None
