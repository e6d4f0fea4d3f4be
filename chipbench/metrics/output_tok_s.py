"""output_tok_s: every output token the window delivered to the host,
over the window's seconds.  The window ends when the step that crosses
its close returns (a closed loop) or when its last request finishes
(an open loop), so the tokens and the time both include that step."""


def read(run):
    n = sum(len(g.times) for g in run.logs.values())
    return n / (run.t_end - run.t0)
