"""The control: the plain reference in the program's place, with its
weights one precision step down (fp8 e4m3 for the stated bfloat16),
fails the logit-gap limit that the program's own run meets: the
harness's own verdict on the control comes out not correct."""

import numpy as np

from chipbench import check


def test_fp8_control_fails_where_the_program_passes(run_tiny):
    """Open loop: every request due is served, so the sample (and the
    readings: program <= 0.01, control ~0.16 on four seeds) do not
    depend on how fast this machine runs."""
    seed, limit, seen = 2 ** 33 + 21, 0.05, {}
    out, lines = run_tiny("repo-complete", seed, limit=limit, seconds=4.0,
                          inspect=seen)
    assert out["correct"], lines
    v, ref, dims = seen["verdict"], seen["ref"], seen["dims"]
    length = seen["run"].cell.traffic["engine"]["max_len"]
    program = max(check.gaps(ref, dims, seed, g, length).max()
                  for g in v.sample)
    ctl = check.check(seen["run"], ref, dims, seed, control=True)
    control = ctl.compared["logit_gap"]["value"]
    assert program == out["compared"]["logit_gap"]["value"]
    assert program <= limit < control
    assert np.isfinite(control)
    assert not ctl.correct and ctl.compared["logit_gap"]["limit"] == limit
    assert ctl.sample == v.sample
