"""Continuous-batching serving engine + variable-length masked attention.

Three correctness pillars:

1. Masked flash attention == lengths-aware reference on ragged lengths,
   including the first-block-fully-masked regression (the `p = exp(0)`
   corruption: with the running max still at NEG_INF, every masked
   entry used to contribute exp(0) == 1 to l/acc).
2. The continuous-batching engine admits/retires requests through a
   small slot pool and matches a lock-step oracle token-for-token —
   the strongest end-to-end check of per-slot positions, ragged
   prefill, and cache insertion across model families.
3. TuneCache merges on-disk entries at save time (concurrent tuners
   must not drop each other's results).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention
from repro.models import Ctx, build_model
from repro.models import layers as L
from repro.plan import KernelConfig
from repro.serve import Request, ServeEngine, lockstep_generate

KEY = jax.random.PRNGKey(0)
CTX = Ctx(plan="jnp", dtype=jnp.float32)


def _qkv(B=2, H=2, S=48, D=16, T=None):
    T = T or S
    kq, kk, kv = jax.random.split(KEY, 3)
    return (jax.random.normal(kq, (B, H, S, D), jnp.float32),
            jax.random.normal(kk, (B, H, T, D), jnp.float32),
            jax.random.normal(kv, (B, H, T, D), jnp.float32))


def _prompts(vocab, lens=(5, 11, 3, 8)):
    return [list(np.random.default_rng(i).integers(0, vocab, n))
            for i, n in enumerate(lens)]


# ----------------------------------------------------------------------
# masked flash attention
# ----------------------------------------------------------------------
@pytest.mark.parametrize("causal", [True, False])
def test_masked_flash_matches_ref_ragged(causal):
    q, k, v = _qkv()
    lens = jnp.array([37, 5], jnp.int32)
    got = flash_attention(q, k, v, q_lens=lens, kv_lens=lens,
                          bq=16, bkv=16, causal=causal, interpret=True)
    want = _ref.flash_attention_ref(q, k, v, causal=causal,
                                    q_lens=lens, kv_lens=lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # rows beyond a sequence's length are exact zeros
    assert bool(jnp.all(got[0, :, 37:] == 0.0))
    assert bool(jnp.all(got[1, :, 5:] == 0.0))


def test_fully_masked_first_block_regression():
    """kv_len == 0: every block is fully masked from the first one on.

    The old kernel computed p = exp(s - m_new) = exp(NEG_INF - NEG_INF)
    = 1 for every masked entry, so l accumulated to S_kv and the output
    became mean(v) instead of zeros.  The guard predicates p on
    m_new > NEG_INF; this test fails on the unguarded kernel.
    """
    q, k, v = _qkv(B=2, H=1, S=16, D=8)
    got = flash_attention(q, k, v,
                          q_lens=jnp.array([16, 16], jnp.int32),
                          kv_lens=jnp.array([0, 16], jnp.int32),
                          bq=8, bkv=8, causal=False, interpret=True)
    # fully-masked sequence: exact zeros (NOT mean(v), which the p=1
    # bug produced — mean(v) of gaussian v is nonzero w.p. 1)
    assert bool(jnp.all(got[0] == 0.0))
    want = _ref.flash_attention_ref(q[1:], k[1:], v[1:], causal=False)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-5)


def test_masked_blocks_after_valid_prefix():
    """Blocks fully masked AFTER a valid prefix (the common ragged case:
    kv_len inside the first of several tiles)."""
    q, k, v = _qkv(B=1, H=1, S=32, D=8)
    lens = jnp.array([5], jnp.int32)
    got = flash_attention(q, k, v, q_lens=lens, kv_lens=lens,
                          bq=8, bkv=8, causal=True, interpret=True)
    want = _ref.flash_attention_ref(q, k, v, causal=True,
                                    q_lens=lens, kv_lens=lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ops_attention_pads_instead_of_fallback(monkeypatch):
    """Non-tile-multiple lengths must stay on the Pallas kernel now —
    run under strict mode so ANY fallback is a hard FallbackError, not
    just the monkeypatched reference exploding."""
    def boom(*a, **kw):
        raise AssertionError("jnp reference fallback taken")
    monkeypatch.setattr(ops._ref, "flash_attention_ref", boom)
    q, k, v = _qkv(B=2, H=2, S=40, D=16)
    with ops.strict_fallbacks():
        got = ops.attention(q, k, v, causal=True, config=KernelConfig(
            backend="interpret", bq=16, bkv=16))
    monkeypatch.undo()
    want = _ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ops_attention_warns_on_remaining_fallback():
    # causal Sq != Skv without lengths is the one intentionally kept
    # fallback (kernel/ref causal alignment differs there)
    q, k, v = _qkv(B=1, H=1, S=16, D=8, T=32)
    with pytest.warns(RuntimeWarning, match="falling back"):
        ops.attention(q, k, v, causal=True, config=KernelConfig(
            backend="interpret", bq=8, bkv=8))


def test_ops_attention_strict_raises_on_remaining_fallback():
    """Strict mode closes the one intentionally-kept fallback: the
    causal Sq != Skv path raises FallbackError unless explicitly
    allowlisted (the paper's contract — no silent reference matmuls)."""
    q, k, v = _qkv(B=1, H=1, S=16, D=8, T=32)
    cfg = KernelConfig(backend="interpret", bq=8, bkv=8)
    with ops.strict_fallbacks():
        with pytest.raises(ops.FallbackError, match="causal"):
            ops.attention(q, k, v, causal=True, config=cfg)
    # per-call strict overrides the ambient mode the same way
    with pytest.raises(ops.FallbackError):
        ops.attention(q, k, v, causal=True, config=cfg, strict=True)
    # the explicit allowlist re-opens exactly this key (warn + ref path)
    with ops.strict_fallbacks(allow=("attention_causal_unaligned",)):
        with pytest.warns(RuntimeWarning, match="falling back"):
            out = ops.attention(q, k, v, causal=True, config=cfg)
    want = _ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # the context restores warn-once mode on exit
    assert not ops._STRICT_FALLBACKS


def test_scatter_at_per_row_positions():
    """(B,) positions write each row at its OWN index (the old code
    collapsed them to pos[0])."""
    c = jnp.zeros((3, 8, 2, 4))
    new = jnp.ones((3, 1, 2, 4))
    pos = jnp.array([1, 5, 7], jnp.int32)
    out = np.asarray(L._scatter_at(c, new, pos))
    for b, p in enumerate([1, 5, 7]):
        assert (out[b, p] == 1.0).all()
        mask = np.ones(8, bool)
        mask[p] = False
        assert (out[b, mask] == 0.0).all()


# ----------------------------------------------------------------------
# Model.prefill == lock-step prompt decode (cache + logits parity)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["gemma-7b", "olmoe-1b-7b", "mamba2-130m",
                                  "zamba2-2.7b", "seamless-m4t-large-v2"])
def test_prefill_matches_decode_loop(arch):
    """Fused prefill must land in the same state as feeding the prompt
    through the decode path token by token (uniform lengths)."""
    cfg = get_config(arch, reduced=True)
    model = build_model(cfg)
    params = model.init(KEY, dtype=jnp.float32)
    B, S, max_len = 2, 12, 24
    tokens = jax.random.randint(jax.random.PRNGKey(7), (B, S), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens,
             "lengths": jnp.full((B,), S, jnp.int32)}
    if cfg.family == "encdec":
        batch["frontend_embeds"] = jax.random.normal(
            KEY, (B, 10, cfg.d_model)) * 0.1
    logits_p, cache_p = model.prefill(params, batch, CTX, max_len)

    cache_l = model.init_cache(B, max_len, jnp.float32)
    if cfg.family == "encdec":
        # lock-step priming of the cross-attention KV (what prefill
        # now does as part of its contract)
        from repro.models import encdec
        enc_out = encdec.encode(params, batch["frontend_embeds"], cfg, CTX)
        ck, cv = [], []
        for i in range(cfg.decoder_layers):
            lp = jax.tree.map(lambda x: x[i], params["decoder"])
            k, v = encdec._enc_kv(lp["cross_attn"], enc_out, cfg, CTX)
            ck.append(k)
            cv.append(v)
        cache_l = dict(cache_l)
        cache_l["cross_k"] = jnp.stack(ck)
        cache_l["cross_v"] = jnp.stack(cv)
    logits_l = None
    for t in range(S):
        logits_l, cache_l = model.decode(params, cache_l,
                                         tokens[:, t:t + 1], CTX)

    if cfg.family == "moe":
        # MoE routing capacity is batch-global: prefill (T = B*S) and
        # per-token decode (T = B) route differently by construction;
        # assert the call contract only.
        assert logits_p.shape == (B, 1, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits_p)))
        return
    np.testing.assert_allclose(np.asarray(logits_p),
                               np.asarray(logits_l),
                               rtol=2e-4, atol=2e-4)
    # the caches must be interchangeable: decode one more token from each
    nxt = jnp.full((B, 1), 3, jnp.int32)
    n_p, _ = model.decode(params, cache_p, nxt, CTX)
    n_l, _ = model.decode(params, cache_l, nxt, CTX)
    np.testing.assert_allclose(np.asarray(n_p), np.asarray(n_l),
                               rtol=2e-4, atol=2e-4)


# ----------------------------------------------------------------------
# continuous batching vs lock-step oracle
# ----------------------------------------------------------------------
@pytest.mark.parametrize("steps_per_dispatch", [1, 4])
@pytest.mark.parametrize("arch", ["gemma-7b", "mamba2-130m", "zamba2-2.7b"])
def test_engine_matches_lockstep_oracle(arch, steps_per_dispatch):
    """Mixed prompt lengths, differing generation lengths, 2 slots for
    4 requests — admission into freed slots must be token-for-token
    identical to decoding everything lock-step in one ragged batch, at
    K=1 AND through the fused K=4 block (every max_new here is
    indivisible by 4, so requests retire mid-block)."""
    cfg = get_config(arch, reduced=True)
    model = build_model(cfg)
    params = model.init(KEY, dtype=jnp.float32)
    prompts = _prompts(cfg.vocab_size)
    max_new = [6, 3, 5, 7]
    engine = ServeEngine(model, params, CTX, num_slots=2, max_len=32,
                         steps_per_dispatch=steps_per_dispatch)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=m)
            for i, (p, m) in enumerate(zip(prompts, max_new))]
    results = engine.run(reqs, step_timeout_s=300.0)
    oracle = lockstep_generate(model, params, CTX, prompts, max_new,
                               max_len=32)
    for i in range(4):
        assert results[i].tokens == oracle[i], (
            f"request {i}: {results[i].tokens} != {oracle[i]}")
    # slot-pool accounting: 4 admissions through <= 2 concurrent slots
    assert engine.stats.admitted == 4
    assert engine.stats.retired == 4
    assert engine.stats.max_concurrent <= 2
    assert engine.stats.prefill_tokens == sum(len(p) for p in prompts)
    # block dispatch amortization: K decode steps per host dispatch
    assert engine.stats.decode_steps == (
        engine.stats.dispatches * steps_per_dispatch)


@pytest.mark.parametrize("steps_per_dispatch", [1, 4])
def test_engine_matches_lockstep_encdec(steps_per_dispatch):
    cfg = get_config("seamless-m4t-large-v2", reduced=True)
    model = build_model(cfg)
    params = model.init(KEY, dtype=jnp.float32)
    S_enc = 12
    frames = np.asarray(
        jax.random.normal(KEY, (4, S_enc, cfg.d_model)) * 0.1)
    prompts = _prompts(cfg.vocab_size)
    max_new = [6, 3, 5, 4]
    engine = ServeEngine(model, params, CTX, num_slots=2, max_len=32,
                         steps_per_dispatch=steps_per_dispatch,
                         cache_kwargs={"enc_len": S_enc})
    reqs = [Request(rid=i, prompt=p, max_new_tokens=m,
                    frontend_embeds=frames[i])
            for i, (p, m) in enumerate(zip(prompts, max_new))]
    results = engine.run(reqs)
    oracle = lockstep_generate(model, params, CTX, prompts, max_new,
                               max_len=32, frontend_embeds=frames)
    for i in range(4):
        assert results[i].tokens == oracle[i]


@pytest.mark.parametrize("steps_per_dispatch", [1, 4])
def test_engine_interpret_stays_on_pallas(monkeypatch, steps_per_dispatch):
    """The acceptance shape: ragged continuous batch under
    impl="interpret" runs the Pallas flash kernel end to end (the jnp
    reference is monkeypatched to explode) and matches the jnp-path
    lock-step oracle token-for-token — including through the fused
    K=4 scan block (max_new=3 retires every request mid-block)."""
    cfg = get_config("gemma-7b", reduced=True)
    model = build_model(cfg)
    params = model.init(KEY, dtype=jnp.float32)
    prompts = _prompts(cfg.vocab_size)
    ctx_i = Ctx(plan=KernelConfig(backend="interpret"), dtype=jnp.float32)

    def boom(*a, **kw):
        raise AssertionError("jnp reference fallback taken on the "
                             "interpret serving path")
    monkeypatch.setattr(ops._ref, "flash_attention_ref", boom)
    engine = ServeEngine(model, params, ctx_i, num_slots=2, max_len=32,
                         steps_per_dispatch=steps_per_dispatch)
    results = engine.run([Request(rid=i, prompt=p, max_new_tokens=3)
                          for i, p in enumerate(prompts)])
    monkeypatch.undo()
    oracle = lockstep_generate(model, params, CTX, prompts, 3, max_len=32)
    for i in range(4):
        assert results[i].tokens == oracle[i]


def test_engine_eos_retires_mid_block():
    """eos hit inside a K=4 block freezes the row on device and the
    host truncates at the eos token — identical to what K=1 emits."""
    cfg = get_config("gemma-7b", reduced=True)
    model = build_model(cfg)
    params = model.init(KEY, dtype=jnp.float32)
    prompts = _prompts(cfg.vocab_size)
    oracle = lockstep_generate(model, params, CTX, prompts, 8, max_len=32)
    # pick an eos id that greedy decode actually emits mid-sequence
    eos = oracle[0][2]

    def truncate(toks):
        return toks[:toks.index(eos) + 1] if eos in toks else toks

    outs = {}
    for K in (1, 4):
        engine = ServeEngine(model, params, CTX, num_slots=2, max_len=32,
                             steps_per_dispatch=K, eos_id=eos)
        results = engine.run([Request(rid=i, prompt=p, max_new_tokens=8)
                              for i, p in enumerate(prompts)])
        outs[K] = [results[i].tokens for i in range(4)]
    assert outs[1] == outs[4]
    for i in range(4):
        assert outs[4][i] == truncate(oracle[i])
    assert outs[4][0][-1] == eos    # request 0 genuinely stopped early
    # the stop is at eos's FIRST occurrence (greedy may emit it earlier
    # than index 2, where it was picked)
    assert len(outs[4][0]) == oracle[0].index(eos) + 1 < 8


def test_lockstep_forced_scores_a_continuation():
    """Teacher-forced on its own tokens the oracle reproduces itself;
    forced onto another token at step j its picks and logits agree with
    the free run up to j, and from j on they judge the forced history
    (which a token-by-token check of that history then sees)."""
    cfg = get_config("gemma-7b", reduced=True)
    model = build_model(cfg)
    params = model.init(KEY, dtype=jnp.float32)
    prompts = _prompts(cfg.vocab_size)
    free, free_lg = lockstep_generate(model, params, CTX, prompts, 6,
                                      max_len=32, return_logits=True)
    same, same_lg = lockstep_generate(model, params, CTX, prompts, 6,
                                      max_len=32, return_logits=True,
                                      forced=free)
    assert same == free
    np.testing.assert_array_equal(same_lg, free_lg)
    j = 2
    other = [list(t) for t in free]
    other[0][j] = (other[0][j] + 1) % cfg.vocab_size
    picks, lg = lockstep_generate(model, params, CTX, prompts, 6,
                                  max_len=32, return_logits=True,
                                  forced=other)
    assert picks[0][:j + 1] == free[0][:j + 1]
    np.testing.assert_array_equal(lg[0, :j + 1], free_lg[0, :j + 1])
    assert not np.array_equal(lg[0, j + 1], free_lg[0, j + 1])
    assert picks[1:] == free[1:]


def test_engine_one_host_sync_per_dispatch(monkeypatch):
    """The zero-stall claim, counted: every device->host readback the
    engine performs goes through engine._host; the decode loop must
    sync exactly once per block dispatch (plus one per admission for
    the prefill-sampled first token), never once per token."""
    from repro.serve import engine as engine_mod
    cfg = get_config("gemma-7b", reduced=True)
    model = build_model(cfg)
    params = model.init(KEY, dtype=jnp.float32)
    prompts = _prompts(cfg.vocab_size)
    counter = {"n": 0}
    real = engine_mod._host

    def counting_host(x):
        counter["n"] += 1
        return real(x)

    monkeypatch.setattr(engine_mod, "_host", counting_host)
    engine = ServeEngine(model, params, CTX, num_slots=2, max_len=32,
                         steps_per_dispatch=4)
    engine.run([Request(rid=i, prompt=p, max_new_tokens=6)
                for i, p in enumerate(prompts)])
    monkeypatch.undo()
    s = engine.stats
    assert counter["n"] == s.admitted + s.dispatches
    # 4 requests x 6 tokens decoded through far fewer syncs than tokens
    assert s.dispatches < s.decode_tokens


def test_engine_seeded_sampling_reproducible_and_block_invariant():
    """Stochastic decode: per-request seeds make output deterministic,
    independent of steps_per_dispatch (the chain advances exactly once
    per emitted token; frozen rows stop advancing), and different
    seeds actually diversify."""
    cfg = get_config("gemma-7b", reduced=True)
    model = build_model(cfg)
    params = model.init(KEY, dtype=jnp.float32)
    prompts = _prompts(cfg.vocab_size)
    max_new = [6, 3, 5, 7]

    def run(K, seed):
        engine = ServeEngine(model, params, CTX, num_slots=2, max_len=32,
                             steps_per_dispatch=K, seed=seed)
        res = engine.run([Request(rid=i, prompt=p, max_new_tokens=m,
                                  temperature=0.9, top_k=20, top_p=0.95)
                          for i, (p, m) in enumerate(zip(prompts, max_new))])
        return [res[i].tokens for i in range(4)]

    a = run(1, seed=7)
    assert run(4, seed=7) == a          # block-size invariant
    assert run(1, seed=7) == a          # reproducible
    b = run(1, seed=8)
    assert a != b                       # seeds diversify (w.h.p.)
    for toks, m in zip(a, max_new):
        assert len(toks) == m
        assert all(0 <= t < cfg.vocab_size for t in toks)


def test_engine_all_greedy_pool_skips_stochastic_sampler():
    """An all-greedy slot pool must dispatch the argmax-specialized
    block (no sorts/PRNG in the hot loop); any stochastic row flips
    the pool to the full sampler block."""
    cfg = get_config("gemma-7b", reduced=True)
    model = build_model(cfg)
    params = model.init(KEY, dtype=jnp.float32)
    prompts = _prompts(cfg.vocab_size)

    def run(temp):
        engine = ServeEngine(model, params, CTX, num_slots=2, max_len=32,
                             steps_per_dispatch=4)
        used = {"full": 0, "greedy": 0}

        def wrap(name, fn):
            def inner(*a):
                used[name] += 1
                return fn(*a)
            return inner

        engine._decode_block = wrap("full", engine._decode_block)
        engine._decode_block_greedy = wrap(
            "greedy", engine._decode_block_greedy)
        engine.run([Request(rid=i, prompt=p, max_new_tokens=4,
                            temperature=temp)
                    for i, p in enumerate(prompts)])
        return used

    used = run(0.0)
    assert used["greedy"] > 0 and used["full"] == 0
    used = run(0.7)
    assert used["full"] > 0 and used["greedy"] == 0


def test_engine_rejects_pending_duplicate_rid():
    """A rid queued but not yet admitted must already be a duplicate —
    the second submit used to be accepted and its result silently
    overwrote the first."""
    cfg = get_config("gemma-7b", reduced=True)
    model = build_model(cfg)
    params = model.init(KEY, dtype=jnp.float32)
    engine = ServeEngine(model, params, CTX, num_slots=1, max_len=32)
    engine.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=2))
    with pytest.raises(ValueError, match="duplicate request id 0"):
        engine.submit(Request(rid=0, prompt=[4, 5], max_new_tokens=2))
    # distinct rid is still fine, and both requests complete
    engine.submit(Request(rid=1, prompt=[4, 5], max_new_tokens=2))
    results = engine.run()
    assert sorted(results) == [0, 1]
    assert results[0].prompt_len == 3   # the FIRST rid-0 request won


def test_engine_moe_serves():
    """MoE: continuous batching runs end-to-end (token-for-token vs a
    differently-composed batch is out of contract — routing capacity
    is batch-global)."""
    cfg = get_config("olmoe-1b-7b", reduced=True)
    model = build_model(cfg)
    params = model.init(KEY, dtype=jnp.float32)
    prompts = _prompts(cfg.vocab_size)
    engine = ServeEngine(model, params, CTX, num_slots=2, max_len=32)
    results = engine.run([Request(rid=i, prompt=p, max_new_tokens=4)
                          for i, p in enumerate(prompts)])
    assert all(len(results[i].tokens) == 4 for i in range(4))


def test_engine_vlm_tight_max_len():
    """Frontend prefix must eat into the prefill bucket budget: with
    max_len sized exactly to prompt + frontend + gen, admission used to
    pad the prompt to a power-of-two bucket and blow past max_len."""
    cfg = get_config("llava-next-34b", reduced=True)
    model = build_model(cfg)
    params = model.init(KEY, dtype=jnp.float32)
    prompt_len, gen = 24, 4
    max_len = prompt_len + cfg.frontend_tokens + gen
    fe = np.asarray(jax.random.normal(
        KEY, (2, cfg.frontend_tokens, cfg.d_model)) * 0.1)
    prompts = _prompts(cfg.vocab_size, lens=(prompt_len, 13))[:2]
    engine = ServeEngine(model, params, CTX, num_slots=2, max_len=max_len)
    results = engine.run([Request(rid=i, prompt=p, max_new_tokens=gen,
                                  frontend_embeds=fe[i])
                          for i, p in enumerate(prompts)])
    oracle = lockstep_generate(model, params, CTX, prompts, gen,
                               max_len=max_len, frontend_embeds=fe)
    for i in range(2):
        assert results[i].tokens == oracle[i]


def test_engine_rejects_oversized_request():
    cfg = get_config("gemma-7b", reduced=True)
    model = build_model(cfg)
    params = model.init(KEY, dtype=jnp.float32)
    engine = ServeEngine(model, params, CTX, num_slots=1, max_len=16)
    with pytest.raises(ValueError, match="exceeds max_len"):
        engine.submit(Request(rid=0, prompt=[1] * 10, max_new_tokens=10))


def test_serve_batch_reports_split_throughput():
    from repro.launch.serve import serve_batch
    out = serve_batch("gemma-7b", reduced=True, batch=4, prompt_len=8,
                      gen_len=4, num_slots=2, mixed=True)
    assert out["generated"].shape == (4, 4)
    assert (np.asarray(out["generated"]) >= 0).all()   # all slots filled
    assert out["prefill_s"] > 0 and out["decode_s"] > 0
    assert out["prefill_tok_s"] > 0 and out["decode_tok_s"] > 0
    # no wasted trailing decode step: N tokens need N-1 decode steps for
    # the longest-lived slot cohort (first token comes from prefill)
    assert out["stats"]["decode_tokens"] < 4 * 4


# ----------------------------------------------------------------------
# tune cache concurrency
# ----------------------------------------------------------------------
def test_tunecache_concurrent_merge(tmp_path):
    from repro.tune import Candidate, TuneCache
    path = os.path.join(tmp_path, "tune.json")
    a, b = TuneCache(path), TuneCache(path)
    cand = Candidate(bm=128, bn=128, bk=128, slots=2, grid_order="ijk")
    a._load()
    b._load()           # both lazily loaded BEFORE either writes
    a.put("ka", cand)
    b.put("kb", cand)   # pre-fix: rewrote the file from b's dict, dropping ka
    fresh = TuneCache(path)
    assert fresh.get("ka") is not None
    assert fresh.get("kb") is not None


def test_host_tiled_matmul_raises_not_asserts():
    a = jnp.zeros((100, 128))
    b = jnp.zeros((128, 128))
    with pytest.raises(ValueError, match="not tiled"):
        ops.host_tiled_matmul(a, b)
