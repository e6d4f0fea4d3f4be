"""The correctness check fails a run whose timed path is broken.

Each test drives a whole run of a tiny cell on the CPU (the harness's
look for a chip skipped) with one fault planted in the program under
it, and sees ``correct`` come out false: a token altered where the
engine produces it, and a decode step that hands back its state
unchanged.  One run per fault, each of both traffic kinds once.
"""

import numpy as np


def test_sound_open_loop_is_correct(run_tiny):
    out, lines = run_tiny("repo-complete", 2 ** 33 + 11)
    assert out["correct"], lines
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert list(out)[-1] == "compared"
    assert lines[-3].startswith("compared logit_gap ")


def test_token_altered_where_produced_is_not_correct(run_tiny, monkeypatch):
    from repro.serve import engine

    real = engine._host

    def altered(x):
        a = real(x)
        return (a + 1) % 512 if a.dtype.kind in "iu" else a
    monkeypatch.setattr(engine, "_host", altered)
    out, lines = run_tiny("batch-gen", 2 ** 33 + 12)
    assert not out["correct"]
    assert out["compared"]["logit_gap"]["value"] > 0.5, lines


def test_state_returned_unchanged_is_not_correct(run_tiny, monkeypatch):
    from repro.serve import engine

    real = engine.ServeEngine._build_block

    def frozen(self, *a, **k):
        block = real(self, *a, **k)

        def step(params, cache, *rest):
            _, toks, keys = block(params, cache, *rest)
            return cache, toks, keys
        return step
    monkeypatch.setattr(engine.ServeEngine, "_build_block", frozen)
    out, lines = run_tiny("repo-complete", 2 ** 33 + 13)
    assert not out["correct"]
    assert np.isfinite(out["compared"]["logit_gap"]["value"])
