"""Paged-KV serving: parity against the lock-step oracle.

The paged engine's contract is that paging is *invisible* to decode
math: on the jnp backend the page-table gather reproduces the
contiguous cache bit-for-bit, so every family that matches
:func:`lockstep_generate` unpaged must still match it paged, at every
``steps_per_dispatch``.  This file locks that down for all five
families (dense, moe, ssm, hybrid, encdec), for seeded stochastic
sampling, for prefix sharing under a shared system prompt, for an
oversubscribed pool (requeue + LRU prefix eviction), and — under
``ops.strict_fallbacks()`` in interpret mode — proves the page-gather
attention path stays on the Pallas kernel.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.models import Ctx, build_model
from repro.plan import KernelConfig
from repro.serve import Request, ServeEngine, lockstep_generate
from repro.serve.paging import OutOfPages

KEY = jax.random.PRNGKey(0)
CTX = Ctx(plan="jnp", dtype=jnp.float32)


@functools.lru_cache(maxsize=None)
def _bundle(arch):
    cfg = get_config(arch, reduced=True)
    model = build_model(cfg)
    params = model.init(KEY, dtype=jnp.float32)
    return cfg, model, params


def _prompts(vocab, lens=(5, 11, 3, 8)):
    return [list(np.random.default_rng(i).integers(0, vocab, n))
            for i, n in enumerate(lens)]


# ----------------------------------------------------------------------
# five-family greedy parity
# ----------------------------------------------------------------------
@pytest.mark.parametrize("steps_per_dispatch", [1, 4])
@pytest.mark.parametrize("arch", ["gemma-7b", "mamba2-130m", "zamba2-2.7b"])
def test_paged_engine_matches_lockstep_oracle(arch, steps_per_dispatch):
    """Same shape as the contiguous-engine oracle test, with the cache
    paged at 4 tokens/page: mixed prompt lengths, 2 slots for 4
    requests, retirement mid-block at K=4.  A family with no pageable
    leaves (pure SSM) must degrade to the contiguous engine with zero
    page gauges; the paged families must actually touch the pool."""
    cfg, model, params = _bundle(arch)
    prompts = _prompts(cfg.vocab_size)
    max_new = [6, 3, 5, 7]
    engine = ServeEngine(model, params, CTX, num_slots=2, max_len=32,
                         steps_per_dispatch=steps_per_dispatch,
                         page_size=4)
    results = engine.run([Request(rid=i, prompt=p, max_new_tokens=m)
                          for i, (p, m) in enumerate(zip(prompts, max_new))])
    oracle = lockstep_generate(model, params, CTX, prompts, max_new,
                               max_len=32)
    for i in range(4):
        assert results[i].tokens == oracle[i], (
            f"request {i}: {results[i].tokens} != {oracle[i]}")
    if cfg.family == "ssm":
        assert not engine._pages_active
        assert engine.stats.pages_in_use == 0
    else:
        assert engine._pages_active
        assert engine.stats.pages_in_use > 0


@pytest.mark.parametrize("steps_per_dispatch", [1, 4])
def test_paged_engine_matches_lockstep_encdec(steps_per_dispatch):
    """encdec: self-attention KV pages, cross-attention KV stays a
    fixed per-slot extent (enc_len must be pinned so the probe cannot
    mistake it for a sequence axis)."""
    cfg, model, params = _bundle("seamless-m4t-large-v2")
    S_enc = 12
    frames = np.asarray(
        jax.random.normal(KEY, (4, S_enc, cfg.d_model)) * 0.1)
    prompts = _prompts(cfg.vocab_size)
    max_new = [6, 3, 5, 4]
    engine = ServeEngine(model, params, CTX, num_slots=2, max_len=32,
                         steps_per_dispatch=steps_per_dispatch,
                         page_size=4, cache_kwargs={"enc_len": S_enc})
    results = engine.run([Request(rid=i, prompt=p, max_new_tokens=m,
                                  frontend_embeds=frames[i])
                          for i, (p, m) in enumerate(zip(prompts, max_new))])
    oracle = lockstep_generate(model, params, CTX, prompts, max_new,
                               max_len=32, frontend_embeds=frames)
    for i in range(4):
        assert results[i].tokens == oracle[i]
    assert engine.stats.pages_in_use > 0


def test_paged_encdec_requires_explicit_enc_len():
    _, model, params = _bundle("seamless-m4t-large-v2")
    with pytest.raises(ValueError, match="enc_len"):
        ServeEngine(model, params, CTX, max_len=32, page_size=4)


@pytest.mark.parametrize("steps_per_dispatch", [1, 4])
def test_paged_moe_matches_unpaged(steps_per_dispatch):
    """MoE routing is batch-global, so the oracle comparison only holds
    for an identically-composed batch: equal-length prompts, equal
    generation lengths, every slot filled at once.  Under that schedule
    the paged engine must match the unpaged one token-for-token."""
    cfg, model, params = _bundle("olmoe-1b-7b")
    prompts = _prompts(cfg.vocab_size, lens=(7, 7))

    def run(**kw):
        engine = ServeEngine(model, params, CTX, num_slots=2, max_len=32,
                             steps_per_dispatch=steps_per_dispatch, **kw)
        res = engine.run([Request(rid=i, prompt=p, max_new_tokens=5)
                          for i, p in enumerate(prompts)])
        return [res[i].tokens for i in range(2)], engine
    unpaged, _ = run()
    paged, engine = run(page_size=4)
    assert paged == unpaged
    assert engine.stats.pages_in_use > 0


def test_paged_seeded_sampling_matches_unpaged():
    """Stochastic decode: the per-request sample chains are a function
    of logits + seeds only, so paging must not perturb them — and the
    paged output stays block-size invariant."""
    cfg, model, params = _bundle("gemma-7b")
    prompts = _prompts(cfg.vocab_size)
    max_new = [6, 3, 5, 7]

    def run(K, **kw):
        engine = ServeEngine(model, params, CTX, num_slots=2, max_len=32,
                             steps_per_dispatch=K, seed=7, **kw)
        res = engine.run([Request(rid=i, prompt=p, max_new_tokens=m,
                                  temperature=0.9, top_k=20, top_p=0.95)
                          for i, (p, m) in enumerate(zip(prompts, max_new))])
        return [res[i].tokens for i in range(4)]

    want = run(1)
    assert run(1, page_size=4) == want
    assert run(4, page_size=4) == want


# ----------------------------------------------------------------------
# prefix sharing + pool pressure
# ----------------------------------------------------------------------
def test_prefix_sharing_shares_pages_and_matches_oracle():
    """Two requests with a shared 16-token system prompt, admitted
    concurrently: the second must map the first's 4 prefix pages into
    its table instead of recomputing/storing them, and both must still
    match the oracle exactly."""
    cfg, model, params = _bundle("gemma-7b")
    sys_prompt = list(range(10, 26))                  # 4 full pages
    prompts = [sys_prompt + [1, 2], sys_prompt + [3, 4, 5]]
    max_new = [4, 3]
    engine = ServeEngine(model, params, CTX, num_slots=2, max_len=32,
                         page_size=4)
    results = engine.run([Request(rid=i, prompt=p, max_new_tokens=m)
                          for i, (p, m) in enumerate(zip(prompts, max_new))])
    oracle = lockstep_generate(model, params, CTX, prompts, max_new,
                               max_len=32)
    for i in range(2):
        assert results[i].tokens == oracle[i]
    # 6 pages (22-token reservation) + 2 own pages for the second
    # request; two isolated requests would peak at 12
    per_req = [math.ceil((len(p) + m) / 4)
               for p, m in zip(prompts, max_new)]
    assert engine.stats.pages_in_use < sum(per_req)
    assert engine.stats.pages_in_use == per_req[0] + 2
    assert engine.stats.pages_shared == 4


def test_oversubscribed_pool_requeues_and_still_matches():
    """A pool smaller than the concurrent working set: admission blocks
    on OutOfPages, evicts cold prefix entries, requeues the request,
    and picks it up once a decode retires — losing no request and no
    tokens."""
    cfg, model, params = _bundle("gemma-7b")
    prompts = _prompts(cfg.vocab_size)
    max_new = [6, 3, 5, 7]
    # full working set needs 3+4+2+4 = 13 pages; give it 9 usable
    engine = ServeEngine(model, params, CTX, num_slots=4, max_len=32,
                         page_size=4, num_pages=10)
    results = engine.run([Request(rid=i, prompt=p, max_new_tokens=m)
                          for i, (p, m) in enumerate(zip(prompts, max_new))])
    oracle = lockstep_generate(model, params, CTX, prompts, max_new,
                               max_len=32)
    for i in range(4):
        assert results[i].tokens == oracle[i]
    assert engine.stats.pages_in_use <= 9
    assert engine.stats.admitted == 4 and engine.stats.retired == 4


def test_exhausted_pool_with_no_active_request_raises():
    """One request that cannot ever fit (2 pages needed, 1 usable) must
    fail loudly instead of requeueing forever."""
    cfg, model, params = _bundle("gemma-7b")
    engine = ServeEngine(model, params, CTX, num_slots=1, max_len=8,
                         page_size=4, num_pages=2)
    engine.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4))
    with pytest.raises(OutOfPages, match="page pool exhausted"):
        engine.run()


# ----------------------------------------------------------------------
# the paged decode path stays on Pallas
# ----------------------------------------------------------------------
@pytest.mark.parametrize("steps_per_dispatch", [1, 4])
def test_paged_interpret_stays_on_pallas(monkeypatch, steps_per_dispatch):
    """Strict-fallback interpret run of the paged engine: the jnp
    attention references are monkeypatched to explode AND strict mode
    turns any silent fallback into a FallbackError, so passing proves
    prefill, the page-table gather decode and the scan block all run
    the Pallas kernels — while matching the jnp-path oracle."""
    cfg, model, params = _bundle("gemma-7b")
    prompts = [[5, 6, 7], [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11], [3, 1]]
    max_new = [5, 4, 6]
    ctx_i = Ctx(plan=KernelConfig(backend="interpret"), dtype=jnp.float32)

    def boom(*a, **kw):
        raise AssertionError("jnp reference fallback taken on the paged "
                             "interpret serving path")
    monkeypatch.setattr(ops._ref, "flash_attention_ref", boom)
    monkeypatch.setattr(ops._ref, "paged_attention_ref", boom,
                        raising=False)
    engine = ServeEngine(model, params, ctx_i, num_slots=2, max_len=32,
                         steps_per_dispatch=steps_per_dispatch,
                         page_size=4)
    with ops.strict_fallbacks():
        results = engine.run([Request(rid=i, prompt=p, max_new_tokens=m)
                              for i, (p, m) in
                              enumerate(zip(prompts, max_new))])
    monkeypatch.undo()
    assert engine._pages_active and engine.stats.pages_in_use > 0
    oracle = lockstep_generate(model, params, CTX, prompts, max_new,
                               max_len=32)
    for i in range(3):
        assert results[i].tokens == oracle[i]


# ----------------------------------------------------------------------
# gauges surface in snapshot(), never in the legacy dict shim
# ----------------------------------------------------------------------
def test_page_gauges_in_snapshot_not_in_legacy_shim():
    from repro.serve.stats import _LEGACY_KEYS
    cfg, model, params = _bundle("gemma-7b")
    engine = ServeEngine(model, params, CTX, num_slots=2, max_len=32,
                         page_size=4)
    engine.run([Request(rid=0, prompt=[4, 5, 6, 7, 8], max_new_tokens=3)])
    snap = engine.stats.snapshot()
    assert snap["pages_in_use"] == engine.stats.pages_in_use > 0
    assert "pages_shared" in snap and "prefill_chunks" in snap
    for key in ("pages_in_use", "pages_shared", "prefill_chunks"):
        assert key not in _LEGACY_KEYS
    with pytest.warns(DeprecationWarning):
        legacy = dict(engine.stats)
    assert set(legacy) == set(_LEGACY_KEYS)


# ----------------------------------------------------------------------
# the paged oracle (lockstep_generate(page_size=...))
# ----------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_paged_oracle_matches_contiguous_oracle(backend):
    """Decoding through paged_view of the prefilled cache must emit the
    contiguous oracle's tokens: bitwise-equal math on jnp, the paged
    Pallas kernel on interpret."""
    cfg, model, params = _bundle("gemma-7b")
    prompts = _prompts(cfg.vocab_size)
    ctx = Ctx(plan=KernelConfig(backend=backend), dtype=jnp.float32)
    base = lockstep_generate(model, params, ctx, prompts, 5, max_len=32)
    paged = lockstep_generate(model, params, ctx, prompts, 5, max_len=32,
                              page_size=4)
    assert paged == base


def test_paged_view_layout():
    """Row b's logical page t is physical page b*T + t, holding exactly
    the contiguous positions [t*ps, (t+1)*ps)."""
    from repro.serve import paged_view
    L_, B, S, KV, D, ps = 2, 3, 8, 2, 4, 4
    k = jnp.arange(L_ * B * S * KV * D, dtype=jnp.float32).reshape(
        L_, B, S, KV, D)
    view = paged_view({"k": k, "v": -k, "pos": jnp.zeros((B,), jnp.int32)},
                      ps)
    T = S // ps
    assert view["k"].shape == (L_, B * T, ps, KV, D)
    assert view["page_table"].shape == (B, T)
    for b in range(B):
        for t in range(T):
            page = int(view["page_table"][b, t])
            np.testing.assert_array_equal(
                view["k"][:, page], k[:, b, t * ps:(t + 1) * ps])
            np.testing.assert_array_equal(
                view["v"][:, page], -k[:, b, t * ps:(t + 1) * ps])
    with pytest.raises(ValueError, match="page_size"):
        paged_view({"k": k, "v": k}, 3)
