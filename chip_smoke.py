#!/usr/bin/env python3
"""Bring-up smoke: deepseek-coder-33b served on one TPU v5e.

Runs the serving path a user runs — ``build_model`` -> ``ServeEngine``
-> ``ops.*`` Pallas kernels — at the model's published widths (d_model
7168, 56 query / 8 KV heads of 128, d_ff 19200, vocab 32256), with
depth cut to 8 of 62 layers and bf16 weights drawn from ``--seed``.
The deployment this stands for: each whole layer on one chip, the 62
layers as ~8 pipeline stages of 8 layers; one chip holds one stage
(~9.4 GB of weights) plus the KV cache of 8 slots x 2048 positions.

Phases (one process, one chip):

  (a) contiguous cache: ServeEngine(num_slots=8, steps_per_dispatch=4)
      serves 16 requests, prompts of 128-1024 tokens, 32 new tokens
      each, greedy;
  (b) paged pool: the same engine with page_size=16, prefill_chunk=256
      serves 8 requests sharing a 256-token system prefix.

Each phase checks (1) the engine's tokens against ``lockstep_generate``
on the same backend and params, teacher-forced on the engine's tokens:
each is the oracle's greedy pick or a rounding-level near-tie (see
TIE_EPS), and (2) the Pallas path's prefill
logits and one following decode step's logits agree with the plain
jnp path computed in float32 at ``highest`` matmul precision on the
same weights.  Everything runs under ``ops.strict_fallbacks()`` with
kernel-dispatch recording on: no fallback may be counted and every
recorded dispatch must be on "pallas".  Any failed check exits
non-zero; the last line of a passing run is one JSON object naming the
device.

  python chip_smoke.py                 # one chip
  python chip_smoke.py --four-chips    # ShardedEngine over 1x4 + Router
                                       # over 4 one-chip replicas

Without a TPU it exits 2 and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "deepseek-coder-33b"
LAYERS = 8

# Logit agreement: relative L2 error ||pallas - ref|| / ||ref|| over all
# compared logits.  The Pallas path computes in bf16 (8-bit mantissa,
# unit roundoff 2^-8 = 0.39%) and the reference in float32 at highest
# precision, so the expected error is a few bf16 roundoffs, ~0.5-1.5%.
# 3% sits above that and well below the two faults it must catch:
# dropping one of the 8 layers moves the logits by tens of percent, and
# weights at fp8 precision (e4m3, 2^-4 roundoff) miss by ~14% (8 layers
# at reduced width on the CPU; bf16 there misses by 1.0%, as on the
# chip).  Both are run below as controls that must fail the bound.
LOGIT_RTOL = 0.03

# Token parity with lockstep_generate.  Engine and oracle run
# differently shaped programs (per-request bucketed prefill vs one
# padded batch), and on the TPU XLA compiles those with different
# rounding: the same rows' prefill logits differ by up to 0.04 between
# a batch-1 and a batch-8 program (measured on a v5e), while every op
# alone is batch-invariant.  Greedy decoding may then flip where the
# top two logits are that close.  So the oracle is teacher-forced on
# the engine's tokens (lockstep_generate(forced=...)) and judges every
# step given the same history: the engine's token must be the oracle's
# greedy pick, or a near-tie within TIE_EPS (2.5x the measured noise)
# of the oracle's best logit.  A request with no difference is
# token-identical to the free-running lockstep_generate.  A scheduling
# fault (wrong slot, position, page or cache row) emits tokens far
# below the oracle's top instead.
TIE_EPS = 0.1


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Traffic and cache sizes of one smoke run."""

    max_len: int = 2048
    num_slots: int = 8
    k: int = 4                          # steps_per_dispatch
    new_tokens: int = 32
    # phase (a): 16 mixed prompts; each oracle group of 8 holds a
    # longest prompt, so every group compiles to the same shape
    prompt_lens: tuple = (1024, 128, 640, 256, 896, 384, 512, 768,
                          1024, 200, 300, 704, 960, 160, 480, 1000)
    buckets: tuple = (256, 512, 1024)
    # phase (b): a shared system prefix + per-request suffixes
    prefix: int = 256
    suffix_lens: tuple = (768, 64, 512, 128, 384, 200, 640, 32)
    page_size: int = 16
    chunk: int = 256
    oracle_group: int = 8
    # logit check
    check_lens: tuple = (256, 192)
    check_max_len: int = 512
    # four-chip phase
    fc_slots: int = 4
    fc_max_len: int = 1024
    fc_prompt_lens: tuple = (512, 128, 384, 256, 448, 200, 320, 96)
    fc_buckets: tuple = (128, 256, 512)
    fc_new_tokens: int = 16


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(AssertionError):
    """A check of the smoke failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    log(f"  check ok: {what}")


def tree_bytes(tree) -> int:
    import jax
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def peak_bytes() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def prompts_of(rng, lens, vocab, prefix=()):
    return [list(prefix) + rng.integers(0, vocab, n).tolist() for n in lens]


def assert_on_kernels(what: str, backend: str = "pallas") -> None:
    """Every kernel dispatch traced since the last reset ran on
    ``backend``, and no fallback was counted."""
    from repro import obs
    from repro.kernels import ops
    recs = obs.recorded_ops()
    backends = sorted({r.backend for r in recs})
    log(f"  {what}: {len(recs)} dispatch signatures, backends {backends}, "
        f"fallbacks {ops.fallback_counts()}")
    check(bool(recs) and backends == [backend],
          f"{what}: every recorded dispatch is on {backend}")
    check(ops.fallback_counts() == {}, f"{what}: zero fallbacks")
    obs.reset_records()


def rel_err(a, r) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    r = np.asarray(r, np.float64)
    return float(np.linalg.norm(a - r) / np.linalg.norm(r))


# ----------------------------------------------------------------------
# checks shared by the phases
# ----------------------------------------------------------------------
def oracle_tokens(model, params, ctx, prompts, results, sizes, max_len,
                  page_size=None):
    """lockstep_generate teacher-forced on the engine's tokens, in
    groups of ``oracle_group`` (bounds the oracle's activations);
    returns the oracle's pick and logits at every step."""
    import numpy as np
    from repro.serve import lockstep_generate
    toks, logits = [], []
    g = sizes.oracle_group
    for i in range(0, len(prompts), g):
        group = prompts[i:i + g]
        forced = [results[i + j].tokens for j in range(len(group))]
        t, lg = lockstep_generate(model, params, ctx, group,
                                  sizes.new_tokens, max_len=max_len,
                                  page_size=page_size, return_logits=True,
                                  forced=forced)
        toks += t
        logits.append(lg)
    return toks, np.concatenate(logits)


def check_tokens(results, oracle, what):
    """Every engine token is the teacher-forced oracle's pick or a
    near-tie in its logits (see TIE_EPS)."""
    picks, logits = oracle
    check(all(len(results[rid].tokens) == len(want)
              for rid, want in enumerate(picks)),
          f"{what}: every request got its {len(picks[0])} tokens")
    ties, n_tok, same = [], 0, 0
    for rid, want in enumerate(picks):
        got = results[rid].tokens
        n_tok += len(got)
        diff = [(rid, j, float(logits[rid, j, b] - logits[rid, j, a]))
                for j, (a, b) in enumerate(zip(got, want)) if a != b]
        same += not diff
        ties += diff
    log(f"  {what}: {same}/{len(picks)} requests token-identical to "
        f"lockstep_generate; {n_tok - len(ties)}/{n_tok} tokens are the "
        f"oracle's greedy pick given the same history; the others (rid, "
        f"step, oracle logit gap): {[(r, j, round(g, 5)) for r, j, g in ties]}")
    check(all(gap <= TIE_EPS for _, _, gap in ties),
          f"{what}: every engine token is the oracle's pick or a near-tie "
          f"(gap <= {TIE_EPS})")


def logits_check(model, params, ctx, sizes, rng, *, page_size=None):
    """Pallas prefill + one decode step vs the float32-highest jnp
    reference on the same weights (relative L2 error <= LOGIT_RTOL).

    Returns ``reference(params) -> (prefill, decode)`` logits and the
    reference's own logits, for the controls."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import obs
    from repro.models import Ctx
    from repro.serve import paged_view

    cfg = model.cfg
    n = sizes.check_lens
    S, L = max(n), sizes.check_max_len
    toks = np.zeros((len(n), S), np.int32)
    for i, ln in enumerate(n):
        toks[i, :ln] = rng.integers(0, cfg.vocab_size, ln)
    batch = {"tokens": jnp.asarray(toks),
             "lengths": jnp.asarray(n, jnp.int32)}

    pal_prefill = jax.jit(lambda p, b: model.prefill(p, b, ctx, L))
    pal_decode = jax.jit(lambda p, c, t: model.decode(p, c, t, ctx))
    pal_pre, cache = pal_prefill(params, batch)
    nxt = jnp.argmax(pal_pre[:, -1], axis=-1).astype(jnp.int32)[:, None]
    if page_size is not None:
        cache = paged_view(cache, page_size)
    pal_dec, _ = pal_decode(params, cache, nxt)
    del cache

    ref_ctx = Ctx(plan="jnp", dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref_prefill = jax.jit(lambda p, b: model.prefill(p, b, ref_ctx, L))
        ref_decode = jax.jit(lambda p, c, t: model.decode(p, c, t, ref_ctx))

    def reference(p):
        """Reference prefill, then decode on the Pallas path's token."""
        was_on = obs.enabled()
        obs.disable()    # the reference runs jnp by design: not recorded
        try:
            with jax.default_matmul_precision("highest"):
                lg, c = ref_prefill(p, batch)
                dl, _ = ref_decode(p, c, nxt)
        finally:
            if was_on:
                obs.enable()
        return lg, dl

    ref = reference(params)
    e_pre, e_dec = rel_err(pal_pre, ref[0]), rel_err(pal_dec, ref[1])
    log(f"  logits vs f32-highest reference: prefill rel L2 {e_pre:.5f}, "
        f"decode rel L2 {e_dec:.5f} (tolerance {LOGIT_RTOL})")
    check(max(e_pre, e_dec) <= LOGIT_RTOL,
          "Pallas logits within tolerance of the reference")
    return reference, ref


def controls(reference, ref, params):
    """Faults the tolerance must catch, through the same reference:
    one layer dropped, then weights at fp8 precision.  The second
    rounds ``params`` in place (donated): run it last."""
    import jax
    import jax.numpy as jnp

    def miss(name, out):
        e = max(rel_err(out[0], ref[0]), rel_err(out[1], ref[1]))
        log(f"  control '{name}': rel L2 {e:.5f}")
        check(e > LOGIT_RTOL, f"control '{name}' fails the tolerance")

    # zeroing the last layer's two norm scales zeroes its attention and
    # MLP inputs, so the layer adds nothing: it is dropped
    layers = dict(params["layers"])
    for norm in ("attn_norm", "mlp_norm"):
        layers[norm] = {"scale": layers[norm]["scale"].at[-1].set(0)}
    miss("one layer dropped", reference({**params, "layers": layers}))
    del layers

    def fp8(w):
        """Round each weight matrix to the e4m3 grid, scaled to its
        range (240: reduce_precision's e4m3 keeps an infinity).  A cast
        pair to float8 and back would be folded away by XLA."""
        if w.ndim < 2:
            return w
        amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=(-2, -1),
                       keepdims=True)
        s = 240.0 / jnp.maximum(amax, 1e-30)
        q = jax.lax.reduce_precision(w.astype(jnp.float32) * s,
                                     exponent_bits=4, mantissa_bits=3)
        return (q / s).astype(w.dtype)
    low = jax.jit(lambda p: jax.tree.map(fp8, p), donate_argnums=0)(params)
    miss("fp8 weights", reference(low))


def serve_phase(name, engine, warmup, requests):
    """Compile by serving ``warmup`` (set-up), then serve ``requests``
    from a fresh stats window; the plan must not grow (no tuner call)."""
    from repro.serve.stats import EngineStats
    t0 = time.perf_counter()
    engine.run(warmup)
    engine.pop_results()
    setup_s = time.perf_counter() - t0
    engine.stats = EngineStats(num_slots=engine.num_slots)
    n_plan = len(engine.plan)
    t0 = time.perf_counter()
    results = engine.run(requests)
    served_s = time.perf_counter() - t0
    s = engine.stats
    log(f"  {name}: warm-up compile {setup_s:.3f} s; served "
        f"{s.retired} requests, {s.prefill_tokens} prompt tokens, "
        f"{s.decode_tokens + s.admitted} generated tokens in "
        f"{served_s:.3f} s ({s.dispatches} dispatches, "
        f"{s.prefill_chunks} prefill chunks, pages in use {s.pages_in_use},"
        f" pages shared {s.pages_shared})")
    check(s.retired == len(requests), f"{name}: every request retired")
    check(len(engine.plan) == n_plan,
          f"{name}: no tuner call in the served run (plan entries "
          f"{n_plan})")
    return results


# ----------------------------------------------------------------------
def setup(seed):
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import build_model

    cfg = dataclasses.replace(get_config(ARCH), n_layers=LAYERS)
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.jit(model.init, static_argnames="dtype")(
        jax.random.PRNGKey(seed), dtype=jnp.bfloat16)
    jax.block_until_ready(params)
    log(f"config: {ARCH} at published widths (d_model {cfg.d_model}, "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads} x {cfg.resolved_head_dim}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}); reduced: n_layers "
        f"62 -> {cfg.n_layers}; bf16 weights from seed {seed}")
    log(f"params: {tree_bytes(params)} bytes, init "
        f"{time.perf_counter() - t0:.3f} s")
    return cfg, model, params


def one_chip(cfg, model, params, ctx, sizes, seed):
    import jax.numpy as jnp
    import numpy as np
    from repro.serve import Request, ServeEngine

    rng = np.random.default_rng(seed)
    vocab = cfg.vocab_size
    common = dict(num_slots=sizes.num_slots, max_len=sizes.max_len,
                  steps_per_dispatch=sizes.k, cache_dtype=jnp.bfloat16,
                  plan="trace")

    def reqs(prompts, base=0, new=sizes.new_tokens):
        return [Request(rid=base + i, prompt=p, max_new_tokens=new)
                for i, p in enumerate(prompts)]

    # (a) contiguous cache -------------------------------------------
    log("phase (a): contiguous cache")
    prompts = prompts_of(rng, sizes.prompt_lens, vocab)
    warm = reqs(prompts_of(rng, sizes.buckets, vocab), base=1000, new=5)
    t0 = time.perf_counter()
    eng = ServeEngine(model, params, ctx, bucket_sizes=sizes.buckets,
                      **common)
    log(f"  engine built (plan traced: {len(eng.plan)} entries) in "
        f"{time.perf_counter() - t0:.3f} s; KV cache "
        f"{tree_bytes(eng.cache)} bytes")
    res = serve_phase("phase (a)", eng, warm, reqs(prompts))
    log(f"  peak_bytes_in_use after serving: {peak_bytes()}")
    assert_on_kernels("phase (a) engine", ctx.plan.backend)
    oracle = oracle_tokens(model, params, eng.ctx, prompts, res, sizes,
                           sizes.max_len)
    check_tokens(res, oracle, "phase (a)")
    del eng
    logits_check(model, params, ctx, sizes, rng)
    assert_on_kernels("phase (a) oracle + logit check", ctx.plan.backend)

    # (b) paged pool, shared system prefix --------------------------
    log("phase (b): paged pool, chunked prefill, shared prefix")
    system = rng.integers(0, vocab, sizes.prefix).tolist()
    prompts = prompts_of(rng, sizes.suffix_lens, vocab, prefix=system)
    warm = reqs(prompts_of(rng, (sizes.chunk + 1,), vocab), base=1000, new=5)
    eng = ServeEngine(model, params, ctx, page_size=sizes.page_size,
                      prefill_chunk=sizes.chunk, bucket_sizes=sizes.buckets,
                      **common)
    log(f"  engine built (plan traced: {len(eng.plan)} entries); page pool "
        f"{tree_bytes(eng.cache)} bytes")
    res = serve_phase("phase (b)", eng, warm, reqs(prompts))
    check(eng.stats.pages_shared > 0, "phase (b): pages_shared > 0")
    log(f"  peak_bytes_in_use after serving: {peak_bytes()}")
    assert_on_kernels("phase (b) engine", ctx.plan.backend)
    oracle = oracle_tokens(model, params, eng.ctx, prompts, res, sizes,
                           sizes.max_len, page_size=sizes.page_size)
    check_tokens(res, oracle, "phase (b)")
    del eng
    reference, ref = logits_check(model, params, ctx, sizes, rng,
                                  page_size=sizes.page_size)
    assert_on_kernels("phase (b) oracle + logit check", ctx.plan.backend)
    log("controls: faults the logit tolerance must catch")
    controls(reference, ref, params)


def four_chips(cfg, model, params, ctx, sizes, seed):
    """ShardedEngine over a 1x4 ('data','model') mesh against the
    single-chip engine, then a Router over 4 one-chip replicas."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.mesh import make_mesh
    from repro.serve import Request, Router, ServeEngine, ShardedEngine

    devices = jax.devices()
    check(len(devices) == 4, f"four chips present ({len(devices)})")
    rng = np.random.default_rng(seed)
    prompts = prompts_of(rng, sizes.fc_prompt_lens, cfg.vocab_size)
    common = dict(num_slots=sizes.fc_slots, max_len=sizes.fc_max_len,
                  steps_per_dispatch=sizes.k, cache_dtype=jnp.bfloat16,
                  plan="trace", bucket_sizes=sizes.fc_buckets)

    def reqs():
        return [Request(rid=i, prompt=p, max_new_tokens=sizes.fc_new_tokens)
                for i, p in enumerate(prompts)]

    log("four chips: single-chip engine (reference)")
    single = ServeEngine(model, params, ctx, **common)
    t0 = time.perf_counter()
    base = single.run(reqs())
    log(f"  served {len(base)} requests in {time.perf_counter() - t0:.3f} s "
        f"(compile included)")

    log("four chips: ShardedEngine over a 1x4 ('data','model') mesh")
    mesh = make_mesh((1, 4), ("data", "model"))
    sharded = ShardedEngine(model, params, ctx, mesh=mesh, **common)
    n_sharded = sum(not x.sharding.is_fully_replicated
                    for x in jax.tree.leaves(sharded.params))
    log(f"  {n_sharded} param leaves sharded over 'model' "
        f"({cfg.n_heads // 4} query heads, {cfg.n_kv_heads // 4} KV heads "
        f"per chip)")
    n = sizes.fc_prompt_lens[0]
    batch = {"tokens": jnp.asarray([prompts[0]], jnp.int32),
             "lengths": jnp.asarray([n], jnp.int32)}
    logits = {}
    for name, eng in (("single", single), ("sharded", sharded)):
        lg, cache = eng._prefill(eng.params, batch)
        nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)[:, None]
        if name == "sharded":
            nxt = logits["single"][2]
        dl, _ = jax.jit(lambda p, c, t, e=eng: model.decode(p, c, t, e.ctx))(
            eng.params, cache, nxt)
        logits[name] = (lg, dl, nxt)
    e_pre = rel_err(logits["sharded"][0], logits["single"][0])
    e_dec = rel_err(logits["sharded"][1], logits["single"][1])
    log(f"  sharded vs single-chip logits: prefill rel L2 {e_pre:.5f}, "
        f"decode rel L2 {e_dec:.5f} (tolerance {LOGIT_RTOL})")
    check(max(e_pre, e_dec) <= LOGIT_RTOL,
          "sharded logits match the single-chip engine")
    assert_on_kernels("sharded engine", ctx.plan.backend)
    del sharded, logits

    log("four chips: Router over 4 one-chip replicas")
    engines = [ServeEngine(model, params, ctx, device=d, **common)
               for d in devices]
    for i, (eng, d) in enumerate(zip(engines, devices)):
        p_dev = jax.tree.leaves(eng.params)[0].devices()
        c_dev = eng.cache["k"].devices()
        log(f"  replica {i}: params on {sorted(map(str, p_dev))}, cache on "
            f"{sorted(map(str, c_dev))}")
        check(p_dev == {d} and c_dev == {d},
              f"replica {i} params and cache on its own chip")
    router = Router(engines)
    t0 = time.perf_counter()
    routed = router.run(reqs())
    placed = sorted({r.replica for r in routed.values()})
    log(f"  routed {len(routed)} requests over replicas {placed} in "
        f"{time.perf_counter() - t0:.3f} s (compile included)")
    bad = [rid for rid in base if routed[rid].tokens != base[rid].tokens]
    check(not bad, f"router tokens == single-chip engine tokens "
                   f"(mismatched rids {bad})")
    check(len(placed) > 1, "requests spread over more than one replica")
    assert_on_kernels("router replicas", ctx.plan.backend)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase and what it is "
                         "compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); nothing "
              f"was run", file=sys.stderr)
        return 2

    import jax.numpy as jnp
    from repro import obs, tune
    from repro.kernels import ops
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import Ctx

    cache_dir = Path(use_compile_cache())
    n_cached = len(list(cache_dir.iterdir())) if cache_dir.is_dir() else 0
    log(f"device: {dev.device_kind} x {len(jax.devices())}; compile cache "
        f"{cache_dir} ({n_cached} entries at start)")
    t_start = time.perf_counter()
    with tempfile.TemporaryDirectory() as tmp, ops.strict_fallbacks():
        # tile picks come from this run's tuner, never a stale cache
        tune.set_cache(tune.TuneCache(Path(tmp) / "tune.json"))
        obs.enable()
        cfg, model, params = setup(args.seed)
        ctx = Ctx(plan="pallas", dtype=jnp.bfloat16)
        if args.four_chips:
            four_chips(cfg, model, params, ctx, Sizes(), args.seed)
        else:
            one_chip(cfg, model, params, ctx, Sizes(), args.seed)
        obs.disable()
    log(f"peak_bytes_in_use: {peak_bytes()}; wall "
        f"{time.perf_counter() - t_start:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
