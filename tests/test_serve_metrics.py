"""ServeEngine latency metrics: TTFT, queue wait, per-token
percentiles, EngineStats typing/shim, split timeouts, obs spans, the
step's span tree and the prefix-hit counter.

The latency tests monkeypatch the engine's module-level clock
(``engine._now``) with a fake that only advances when the wrapped
prefill/decode callables run, each by a fixed synthetic cost — so
every recorded latency is an exact, deterministic number and the
K-invariance claims become equality assertions instead of tolerances.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.models import Ctx, build_model
from repro.serve import EngineStats, Request, Router, ServeEngine
from repro.serve import engine as engine_mod
from repro.serve.paging import OutOfPages
from repro.serve.stats import _LEGACY_KEYS

KEY = jax.random.PRNGKey(0)
CTX = Ctx(plan="jnp", dtype=jnp.float32)

PREFILL_C = 0.5    # synthetic per-admission prefill cost (fake seconds)
DECODE_C = 0.125   # synthetic per-decode-iteration cost


@pytest.fixture(scope="module")
def bundle():
    cfg = get_config("gemma-7b", reduced=True)
    model = build_model(cfg)
    params = model.init(KEY, dtype=jnp.float32)
    return cfg, model, params


class FakeClock:
    """Returns a fixed time until explicitly advanced."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _instrument(engine: ServeEngine, clock: FakeClock) -> None:
    """Make the fake clock advance by the synthetic costs: PREFILL_C
    per admission prefill, DECODE_C per fused decode iteration (so a
    K-step block costs K * DECODE_C, like K single steps would)."""
    prefill, block, block_g = (engine._prefill, engine._decode_block,
                               engine._decode_block_greedy)

    def timed_prefill(p, batch):
        clock.advance(PREFILL_C)
        return prefill(p, batch)

    def timed_block(fn):
        def run(*args):
            clock.advance(engine.steps_per_dispatch * DECODE_C)
            return fn(*args)
        return run

    engine._prefill = timed_prefill
    engine._decode_block = timed_block(block)
    engine._decode_block_greedy = timed_block(block_g)


def _engine(model, params, clock, **kw):
    eng = ServeEngine(model, params, CTX, max_len=32, **kw)
    _instrument(eng, clock)
    return eng


def _prompts(vocab, lens=(5, 11, 3, 8)):
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1),
                                         (len(lens), max(lens)), 0, vocab))
    return [toks[i, :n].tolist() for i, n in enumerate(lens)]


# ----------------------------------------------------------------------
# determinism + K-invariance
# ----------------------------------------------------------------------
@pytest.mark.parametrize("steps_per_dispatch", [1, 4])
def test_latency_metrics_deterministic_under_fake_clock(
        bundle, monkeypatch, steps_per_dispatch):
    """Same workload + fake clock twice -> bit-identical snapshots."""
    cfg, model, params = bundle
    snaps = []
    for _ in range(2):
        clock = FakeClock()
        monkeypatch.setattr(engine_mod, "_now", clock)
        eng = _engine(model, params, clock, num_slots=2,
                      steps_per_dispatch=steps_per_dispatch)
        eng.run([Request(rid=i, prompt=p, max_new_tokens=m)
                 for i, (p, m) in enumerate(zip(_prompts(cfg.vocab_size),
                                                (6, 3, 5, 7)))])
        snaps.append(eng.stats.snapshot())
    assert snaps[0] == snaps[1]


def test_ttft_and_token_p99_invariant_across_k(bundle, monkeypatch):
    """With every request admitted in step 1 (slots >= requests), TTFT
    depends only on admission order — exactly equal for K=1 and K=4 —
    and per-token latency is each gap between a request's consecutive
    decode emissions, which a K-block delivers together."""
    cfg, model, params = bundle
    per_req, summaries, gaps = {}, {}, {}
    for k in (1, 4):
        clock = FakeClock()
        monkeypatch.setattr(engine_mod, "_now", clock)
        eng = _engine(model, params, clock, num_slots=4,
                      steps_per_dispatch=k)
        results = eng.run(
            [Request(rid=i, prompt=p, max_new_tokens=m)
             for i, (p, m) in enumerate(zip(_prompts(cfg.vocab_size),
                                            (6, 3, 5, 7)))])
        per_req[k] = {r.rid: (r.ttft_s, r.queue_wait_s)
                      for r in results.values()}
        summaries[k] = eng.stats.latency_summary()
        gaps[k] = collections.Counter(eng.stats.token_latency_s)
    assert per_req[1] == per_req[4]
    assert summaries[1]["ttft"] == summaries[4]["ttft"]
    # i-th admission of the first step: i prior prefills in front of it
    assert per_req[1][0] == (PREFILL_C, 0.0)
    assert per_req[1][3] == (4 * PREFILL_C, 3 * PREFILL_C)
    # 5 + 2 + 4 + 6 decode tokens; a request's first decode token has
    # no earlier decode emission, so 17 - 4 gaps.  K=1: one block per
    # token, every gap DECODE_C.  K=4: a block's first token arrives
    # 4 * DECODE_C after the previous block's tokens, the rest at 0
    # (rids 0 and 3 take a second block)
    assert gaps[1] == {DECODE_C: 13}
    assert gaps[4] == {4 * DECODE_C: 2, 0.0: 11}
    tok = summaries[1]["token_latency"]
    assert tok["p50"] == tok["p99"] == tok["max"] == DECODE_C
    assert summaries[4]["token_latency"]["max"] == 4 * DECODE_C


def test_queue_wait_for_mid_run_admission(bundle, monkeypatch):
    """A request that waits for a slot accrues queue time equal to the
    clock interval between submit and admission — exactly."""
    cfg, model, params = bundle
    clock = FakeClock()
    monkeypatch.setattr(engine_mod, "_now", clock)
    eng = _engine(model, params, clock, num_slots=1, steps_per_dispatch=1)
    prompts = _prompts(cfg.vocab_size)
    eng.submit(Request(rid=0, prompt=prompts[0], max_new_tokens=3))
    eng.submit(Request(rid=1, prompt=prompts[1], max_new_tokens=2))
    results = eng.run()
    # rid 0: admitted at t=0; 3 tokens = prefill + 2 decode steps
    assert results[0].queue_wait_s == 0.0
    assert results[0].ttft_s == PREFILL_C
    # rid 1 is admitted on the step after rid 0 retires: it spent the
    # whole of rid 0's service time (prefill + 2 decode blocks) queued
    assert results[1].queue_wait_s == PREFILL_C + 2 * DECODE_C
    assert results[1].ttft_s == results[1].queue_wait_s + PREFILL_C
    assert eng.stats.queue_wait_s == [0.0, PREFILL_C + 2 * DECODE_C]


def test_dispatch_occupancy_samples(bundle, monkeypatch):
    cfg, model, params = bundle
    clock = FakeClock()
    monkeypatch.setattr(engine_mod, "_now", clock)
    eng = _engine(model, params, clock, num_slots=2, steps_per_dispatch=1)
    eng.run([Request(rid=0, prompt=_prompts(cfg.vocab_size)[0],
                     max_new_tokens=3)])
    # one active request in a 2-slot pool: every dispatch half-occupied
    assert eng.stats.dispatch_occupancy == [0.5, 0.5]
    assert eng.stats.mean_dispatch_occupancy == 0.5


# ----------------------------------------------------------------------
# split prefill/decode timeouts (satellite fix)
# ----------------------------------------------------------------------
def test_slow_prefill_does_not_trip_decode_budget(bundle, monkeypatch):
    """The historical bug: one step_timeout_s wrapped admission prefill
    AND decode, so a long prompt's prefill tripped the decode budget.
    A slow prefill must only fail the *prefill* budget now."""
    cfg, model, params = bundle
    prompts = _prompts(cfg.vocab_size)

    def fresh():
        clock = FakeClock()
        monkeypatch.setattr(engine_mod, "_now", clock)
        return _engine(model, params, clock, num_slots=2)

    reqs = [Request(rid=i, prompt=p, max_new_tokens=3)
            for i, p in enumerate(prompts[:2])]
    # prefill costs 0.5 fake-s > 0.3 decode budget: must NOT raise
    fresh().run(reqs, decode_timeout_s=0.3)
    # but it does exceed an explicit prefill budget
    with pytest.raises(RuntimeError, match="prefill_timeout_s"):
        fresh().run(reqs, prefill_timeout_s=0.3)
    # a decode block over budget still fails, naming decode
    with pytest.raises(RuntimeError, match="decode_timeout_s"):
        fresh().run(reqs, decode_timeout_s=DECODE_C / 2)
    # step_timeout_s remains shorthand for both budgets
    with pytest.raises(RuntimeError, match="prefill_timeout_s"):
        fresh().run(reqs, step_timeout_s=0.3)
    fresh().run(reqs, step_timeout_s=10.0)


# ----------------------------------------------------------------------
# EngineStats typing + deprecation shim (satellite)
# ----------------------------------------------------------------------
def test_stats_is_typed_with_derived_throughput(bundle):
    cfg, model, params = bundle
    eng = ServeEngine(model, params, CTX, num_slots=2, max_len=32)
    eng.run([Request(rid=i, prompt=p, max_new_tokens=3)
             for i, p in enumerate(_prompts(cfg.vocab_size))])
    s = eng.stats
    assert isinstance(s, EngineStats)
    assert s.decode_tok_s == s.decode_tokens / max(s.decode_s, 1e-9)
    assert s.prefill_tok_s == s.prefill_tokens / max(s.prefill_s, 1e-9)
    assert 0 < s.mean_dispatch_occupancy <= 1
    snap = s.snapshot()
    assert snap["admitted"] == 4 and snap["num_slots"] == 2
    assert snap["ttft"]["n"] == 4
    # one gap per decode token after each request's first decode token
    assert snap["token_latency"]["n"] == s.decode_tokens - 4
    # engine.throughput() stays consistent with the typed stats
    assert eng.throughput()["decode_tok_s"] == s.decode_tok_s


def test_stats_dict_shim_parity_and_deprecation(bundle):
    cfg, model, params = bundle
    eng = ServeEngine(model, params, CTX, num_slots=2, max_len=32)
    eng.run([Request(rid=0, prompt=_prompts(cfg.vocab_size)[0],
                     max_new_tokens=3)])
    with pytest.warns(DeprecationWarning, match="snapshot"):
        legacy = dict(eng.stats)
    # parity: the shim serves exactly the original dict's key set
    assert set(legacy) == set(_LEGACY_KEYS)
    assert legacy == {k: getattr(eng.stats, k) for k in _LEGACY_KEYS}
    with pytest.warns(DeprecationWarning):
        assert eng.stats["decode_steps"] == eng.stats.decode_steps
    with pytest.warns(DeprecationWarning):
        eng.stats["decode_steps"] = 99
    assert eng.stats.decode_steps == 99
    with pytest.warns(DeprecationWarning):
        with pytest.raises(KeyError):
            eng.stats["ttft"]          # only legacy keys ride the shim
    assert "dispatches" in eng.stats and "ttft" not in eng.stats


# ----------------------------------------------------------------------
# obs spans/events from the engine
# ----------------------------------------------------------------------
def test_engine_emits_spans_and_retire_events(bundle):
    cfg, model, params = bundle
    eng = ServeEngine(model, params, CTX, num_slots=2, max_len=32)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=3)
            for i, p in enumerate(_prompts(cfg.vocab_size))]
    with obs.capture() as sink:
        eng.run(reqs)
    names = [r["name"] for r in sink.records]
    assert names.count("serve.admit") == 4
    assert names.count("serve.retire") == 4
    assert names.count("serve.dispatch") == eng.stats.dispatches
    admit = next(r for r in sink.records if r["name"] == "serve.admit")
    assert admit["type"] == "span" and admit["prompt_len"] == len(reqs[0].prompt)
    retire = next(r for r in sink.records if r["name"] == "serve.retire")
    assert retire["tokens"] == 3


# ----------------------------------------------------------------------
# the step's span tree and the prefix-hit counter (paged engine)
# ----------------------------------------------------------------------
PS = 4   # tokens per page


def _shared_prefix_prompts(vocab):
    """Three prompts over one 8-token prefix: two keep all of it, one
    only its first 6 tokens."""
    a, b, c = _prompts(vocab, (11, 12, 10))
    prefix = a[:8]
    return [a, prefix + b[:4], prefix[:6] + c[:4]]


def _full_page_overlap(prompts, ps):
    """Prompt tokens the prefix cache serves when the prompts are
    installed in order: each one's longest full-page prefix shared with
    an earlier prompt."""
    def lcp(a, b):
        n = 0
        while n < min(len(a), len(b)) and a[n] == b[n]:
            n += 1
        return n
    return sum(max((lcp(p, q) // ps * ps for q in prompts[:i]), default=0)
               for i, p in enumerate(prompts))


def _within(inner, outer):
    return (outer["t"] <= inner["t"] and inner["t"] + inner["dur_s"]
            <= outer["t"] + outer["dur_s"])


@pytest.mark.parametrize("prefill_chunk,fail_once", [
    (None, False), (8, False), (None, True), (8, True)])
def test_step_span_tree_and_prefix_hits(bundle, monkeypatch, prefill_chunk,
                                        fail_once):
    """Every span lies inside a ``serve.step``; every ``serve.sync``
    inside ``serve.dispatch`` or ``serve.first_token``, one per device
    read; ``prefix_hit_tokens`` is the hand-computed full-page overlap,
    also when the second install first fails with ``OutOfPages`` (a
    requeued or parked admission counts once, when it lands)."""
    cfg, model, params = bundle
    reads = []
    host = engine_mod._host

    def counted(x):
        reads.append(1)
        return host(x)
    monkeypatch.setattr(engine_mod, "_host", counted)
    eng = ServeEngine(model, params, CTX, num_slots=4, max_len=32,
                      page_size=PS, prefill_chunk=prefill_chunk,
                      steps_per_dispatch=2)
    if fail_once:
        alloc, calls = eng._alloc_pages, []

        def flaky(n):
            calls.append(n)
            if len(calls) == 2:
                raise OutOfPages("one-shot pool pressure")
            return alloc(n)
        eng._alloc_pages = flaky
    prompts = _shared_prefix_prompts(cfg.vocab_size)
    with obs.capture() as sink:
        results = eng.run([Request(rid=i, prompt=p, max_new_tokens=3)
                           for i, p in enumerate(prompts)])
    assert all(len(r.tokens) == 3 for r in results.values())
    spans = [r for r in sink.records if r["type"] == "span"]
    steps = [s for s in spans if s["name"] == "serve.step"]
    assert [s["step"] for s in steps] == list(range(1, eng._step + 1))
    assert all(any(_within(s, st) for st in steps) for s in spans)
    syncs = [s for s in spans if s["name"] == "serve.sync"]
    holders = [s for s in spans
               if s["name"] in ("serve.dispatch", "serve.first_token")]
    assert all(any(_within(s, h) for h in holders) for s in syncs)
    assert len(syncs) == len(reads)
    assert collections.Counter(s["what"] for s in syncs) == {
        "first_token": 3, "decode_block": eng.stats.dispatches}
    assert {s["name"] for s in spans} >= {
        "serve.step", "serve.install", "serve.first_token", "serve.sync",
        "serve.dispatch", "serve.launch", "serve.emit"}
    for inner, outer in (("serve.launch", "serve.dispatch"),
                         ("serve.install", "serve.step")):
        assert all(any(_within(s, o) for o in spans if o["name"] == outer)
                   for s in spans if s["name"] == inner)
    hits = _full_page_overlap(prompts, PS)
    assert hits == 12
    assert eng.stats.prefix_hit_tokens == hits
    assert eng.stats.snapshot()["prefix_hit_tokens"] == hits
    assert eng.stats.prefill_tokens == sum(map(len, prompts))


def test_merge_and_router_sum_prefix_hits(bundle):
    cfg, model, params = bundle
    parts = [EngineStats(prefix_hit_tokens=n) for n in (3, 0, 5)]
    assert EngineStats.merge(parts).prefix_hit_tokens == 8
    engines = [ServeEngine(model, params, CTX, num_slots=2, max_len=32,
                           page_size=PS) for _ in range(2)]
    router = Router(engines)
    prompts = _shared_prefix_prompts(cfg.vocab_size)
    router.run([Request(rid=i, prompt=p, max_new_tokens=2)
                for i, p in enumerate(prompts + prompts[:1])])
    per = [e.stats.prefix_hit_tokens for e in engines]
    assert sum(per) > 0
    assert router.stats().prefix_hit_tokens == sum(per)
    assert router.snapshot()["prefix_hit_tokens"] == sum(per)
