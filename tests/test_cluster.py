"""repro.serve.cluster: replica Router + ShardedEngine contracts.

The router's headline claim is *placement-independent tokens*: a fleet
of N equal-seed replicas must produce exactly the token streams one
engine produces — for every model family, greedy and seeded sampling,
at every ``steps_per_dispatch``, and across a replica dying mid-stream
(kill API, step timeout, lost heartbeat) with its work re-queued onto
survivors.  Streaming consumers additionally never see a duplicate or
a gap (at-most-once emission across the replay).

ShardedEngine gets the same treatment: tokens identical to the plain
engine on a 1-device mesh in-process, and on 8 forced CPU devices in a
subprocess (the test_distributed idiom) with params actually sharded.
"""

import functools
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import Ctx, build_model
from repro.plan import Plan
from repro.runtime.fault_tolerance import RetryPolicy, TransientError
from repro.serve import Request, Router, ServeEngine
from repro.serve import engine as engine_mod
from repro.serve.cluster import (ReplicaTimeout, RequeueExhausted,
                                 ShardedEngine)

KEY = jax.random.PRNGKey(0)
CTX = Ctx(plan="jnp", dtype=jnp.float32)
SRC = os.path.join(os.path.dirname(__file__), "..", "src")

ENC_LEN = 12  # encdec encoder frames per request


@functools.lru_cache(maxsize=None)
def _bundle(arch):
    cfg = get_config(arch, reduced=True)
    model = build_model(cfg)
    params = model.init(KEY, dtype=jnp.float32)
    return cfg, model, params


def _prompts(vocab, lens):
    return [list(np.random.default_rng(i).integers(0, vocab, n))
            for i, n in enumerate(lens)]


def _requests(cfg, lens, max_new, frames=None):
    """Mixed trace: even rids greedy, odd rids sampled (rid 3 with an
    explicit seed, the rest on the engine's fold_in(seed, rid) chain —
    the placement-independence contract either way)."""
    prompts = _prompts(cfg.vocab_size, lens)
    reqs = []
    for i, (p, m) in enumerate(zip(prompts, max_new)):
        kw = {}
        if i % 2:
            kw = dict(temperature=0.8, top_k=8, top_p=0.9)
            if i == 3:
                kw["seed"] = 123
        if frames is not None:
            kw["frontend_embeds"] = frames[i]
        reqs.append(Request(rid=i, prompt=p, max_new_tokens=m, **kw))
    return reqs


def _family_fixture(arch):
    """(engine kwargs, request frames) for one family.  MoE routing is
    batch-global, so parity needs identical batch composition: one slot
    per engine makes every batch a single request on both sides."""
    cfg, model, params = _bundle(arch)
    ekw = {"num_slots": 1 if cfg.family == "moe" else 2, "max_len": 32}
    frames = None
    if cfg.family == "encdec":
        ekw["cache_kwargs"] = {"enc_len": ENC_LEN}
        frames = np.asarray(
            jax.random.normal(KEY, (6, ENC_LEN, cfg.d_model)) * 0.1)
    return cfg, model, params, ekw, frames


def _stream_checker():
    """on_token collector + the no-duplicate/no-gap assertion helper."""
    streamed = {}

    def on_token(rid, tok):
        streamed.setdefault(rid, []).append(tok)
    return streamed, on_token


# ----------------------------------------------------------------------
# five-family parity: N replicas == one engine
# ----------------------------------------------------------------------
@pytest.mark.parametrize("steps_per_dispatch", [1, 4])
@pytest.mark.parametrize("arch", ["gemma-7b", "mamba2-130m",
                                  "zamba2-2.7b", "seamless-m4t-large-v2",
                                  "olmoe-1b-7b"])
def test_router_matches_single_engine(arch, steps_per_dispatch):
    cfg, model, params, ekw, frames = _family_fixture(arch)
    lens, max_new = (5, 11, 3, 8, 6, 9), (6, 3, 5, 7, 4, 6)

    baseline = ServeEngine(model, params, CTX,
                           steps_per_dispatch=steps_per_dispatch,
                           **ekw).run(_requests(cfg, lens, max_new, frames))

    engines = [ServeEngine(model, params, CTX,
                           steps_per_dispatch=steps_per_dispatch, **ekw)
               for _ in range(3)]
    router = Router(engines)
    streamed, on_token = _stream_checker()
    results = router.run(_requests(cfg, lens, max_new, frames),
                         on_token=on_token)

    for i in range(6):
        assert results[i].tokens == baseline[i].tokens, (
            f"request {i} placement-dependent: "
            f"{results[i].tokens} != {baseline[i].tokens}")
        assert streamed[i] == results[i].tokens   # no dup, no gap
    # work actually spread over the fleet
    assert len({results[i].replica for i in range(6)}) > 1
    # replica_id tagging + fleet aggregate
    snap = router.snapshot()
    assert [p["replica_id"] for p in snap["per_replica"]] == [0, 1, 2]
    fleet = router.stats()
    assert fleet.admitted == fleet.retired == 6
    assert fleet.admitted == sum(p["admitted"] for p in snap["per_replica"])
    assert snap["router"]["deaths"] == 0 and snap["router"]["requeues"] == 0


# ----------------------------------------------------------------------
# load-aware placement
# ----------------------------------------------------------------------
def test_placement_fills_emptiest_pool_first():
    cfg, model, params = _bundle("gemma-7b")
    lens = (5, 5, 5, 5, 5, 5)
    engines = [ServeEngine(model, params, CTX, num_slots=2, max_len=32)
               for _ in range(3)]
    router = Router(engines)
    results = router.run(_requests(cfg, lens, [4] * 6))
    # 6 equal requests over 3x2 slots: net-free-capacity ordering gives
    # exact round-robin, two per replica
    assert sorted(results[i].replica for i in range(6)) == [0, 0, 1, 1, 2, 2]
    # the rid tie-break: the very first request of a fresh fleet lands
    # on replica 0
    assert results[0].replica == 0


def test_placement_breaks_slot_ties_by_page_occupancy():
    cfg, model, params = _bundle("gemma-7b")
    prompts = _prompts(cfg.vocab_size, (8, 8))
    engines = [ServeEngine(model, params, CTX, num_slots=2, max_len=32,
                           page_size=4) for _ in range(2)]
    router = Router(engines)
    # warm replica 0's prefix cache: the retired request's pages stay
    # referenced by the cache, so its pool reads busier at equal slots
    engines[0].run([Request(rid=90, prompt=prompts[0], max_new_tokens=2)])
    assert engines[0].pages_in_use_now > 0
    assert engines[0].free_slots == engines[1].free_slots
    router.submit(Request(rid=0, prompt=prompts[1], max_new_tokens=8))
    router.step()
    assert not router.replicas[0].inflight
    assert 0 in router.replicas[1].inflight


# ----------------------------------------------------------------------
# fault paths: kill, step timeout, heartbeat loss
# ----------------------------------------------------------------------
def _parity_after_fault(router, cfg, lens, max_new, fault_at, fault):
    """Drive the router manually, inject `fault` after step `fault_at`,
    and return (results, streamed)."""
    for r in _requests(cfg, lens, max_new):
        router.submit(r)
    streamed, on_token = _stream_checker()
    steps = 0
    while not router.idle:
        for rid, tok in router.step():
            on_token(rid, tok)
        steps += 1
        if steps == fault_at:
            fault()
    return router.results, streamed


def test_kill_midstream_replays_without_duplicates():
    cfg, model, params = _bundle("gemma-7b")
    lens, max_new = (5, 11, 3, 8), (8, 8, 8, 8)
    baseline = ServeEngine(model, params, CTX, num_slots=2,
                           max_len=32).run(_requests(cfg, lens, max_new))
    engines = [ServeEngine(model, params, CTX, num_slots=2, max_len=32)
               for _ in range(2)]
    router = Router(engines)
    results, streamed = _parity_after_fault(
        router, cfg, lens, max_new, fault_at=2, fault=lambda: router.kill(0))
    for i in range(4):
        assert results[i].tokens == baseline[i].tokens
        assert streamed[i] == results[i].tokens
        assert results[i].replica == 1       # only the survivor finishes
    assert router.deaths == 1
    assert router.requeues == 2              # replica 0's two slots
    assert router.snapshot()["router"]["alive"] == 1


def test_step_timeout_kills_and_replays(monkeypatch):
    """A replica whose fused dispatch blows step_timeout_s dies
    (ReplicaTimeout — deliberately NOT a TransientError: the step
    already advanced the engine, an in-place retry would lose tokens)
    and its requests replay on the survivor, token-identically."""
    assert not issubclass(ReplicaTimeout, TransientError)
    cfg, model, params = _bundle("gemma-7b")
    lens, max_new = (5, 11, 3, 8), (6, 6, 6, 6)
    baseline = ServeEngine(model, params, CTX, num_slots=2,
                           max_len=32).run(_requests(cfg, lens, max_new))

    class FakeClock:
        t = 0.0

        def __call__(self):
            return self.t
    clock = FakeClock()
    monkeypatch.setattr(engine_mod, "_now", clock)
    engines = [ServeEngine(model, params, CTX, num_slots=2, max_len=32)
               for _ in range(2)]
    # only replica 0's decode block consumes (fake) wall-clock
    for name in ("_decode_block", "_decode_block_greedy"):
        fn = getattr(engines[0], name)

        def slow(*args, _fn=fn):
            clock.t += 10.0
            return _fn(*args)
        setattr(engines[0], name, slow)

    router = Router(engines, step_timeout_s=1.0)
    streamed, on_token = _stream_checker()
    for r in _requests(cfg, lens, max_new):
        router.submit(r)
    results = router.run(on_token=on_token)
    for i in range(4):
        assert results[i].tokens == baseline[i].tokens
        assert streamed[i] == results[i].tokens
        assert results[i].replica == 1
    assert router.deaths == 1 and not router.replicas[0].alive


def test_heartbeat_loss_kills_and_replays():
    cfg, model, params = _bundle("gemma-7b")
    lens, max_new = (5, 11), (6, 6)
    baseline = ServeEngine(model, params, CTX, num_slots=1,
                           max_len=32).run(_requests(cfg, lens, max_new))
    engines = [ServeEngine(model, params, CTX, num_slots=1, max_len=32)
               for _ in range(2)]
    import tempfile
    with tempfile.TemporaryDirectory() as hb_dir:
        router = Router(engines, heartbeat_dir=hb_dir,
                        heartbeat_timeout_s=60.0)

        def lose_heartbeat():
            # rewind replica 0's heartbeat far past the timeout
            path = router.replicas[0].executor.heartbeat.path
            with open(path) as f:
                hb = json.load(f)
            hb["t"] -= 1000.0
            with open(path, "w") as f:
                json.dump(hb, f)
        results, streamed = _parity_after_fault(
            router, cfg, lens, max_new, fault_at=2, fault=lose_heartbeat)
    for i in range(2):
        assert results[i].tokens == baseline[i].tokens
        assert streamed[i] == results[i].tokens
    assert router.deaths == 1 and not router.replicas[0].alive


def test_fresh_replica_not_killed_before_first_beat():
    """A replica that never beat yet is starting, not stale: with a
    heartbeat timeout configured, admission + first step must succeed
    even though no heartbeat file exists at dispatch time."""
    cfg, model, params = _bundle("gemma-7b")
    engines = [ServeEngine(model, params, CTX, num_slots=2, max_len=32)]
    import tempfile
    with tempfile.TemporaryDirectory() as hb_dir:
        router = Router(engines, heartbeat_dir=hb_dir,
                        heartbeat_timeout_s=1e-9)
        router.submit(Request(
            rid=0, prompt=_prompts(cfg.vocab_size, (5,))[0],
            max_new_tokens=2))
        router.step()
    assert router.replicas[0].alive
    assert router.replicas[0].inflight or router.results


# ----------------------------------------------------------------------
# budget exhaustion + no survivors
# ----------------------------------------------------------------------
def test_requeue_budget_exhaustion_is_fatal():
    cfg, model, params = _bundle("gemma-7b")
    engines = [ServeEngine(model, params, CTX, num_slots=1, max_len=32)
               for _ in range(2)]
    router = Router(engines, policy=RetryPolicy(
        max_retries=1, restart_on_exhaustion=False))
    router.submit(Request(rid=0, prompt=_prompts(cfg.vocab_size, (5,))[0],
                          max_new_tokens=20))
    router.step()
    router.kill(0)         # first replay: within the budget of 1
    router.step()          # re-placed on replica 1
    with pytest.raises(RequeueExhausted, match="budget exhausted"):
        router.kill(1)     # second death: out of budget


def test_no_surviving_replicas_raises():
    cfg, model, params = _bundle("gemma-7b")
    router = Router([ServeEngine(model, params, CTX, num_slots=1,
                                 max_len=32)])
    router.submit(Request(rid=0, prompt=_prompts(cfg.vocab_size, (5,))[0],
                          max_new_tokens=20))
    router.step()
    router.kill(0)
    with pytest.raises(RuntimeError, match="no alive replicas"):
        router.step()


# ----------------------------------------------------------------------
# construction contracts + static validation
# ----------------------------------------------------------------------
def test_router_rejects_mismatched_or_shared_engines():
    cfg, model, params = _bundle("gemma-7b")
    eng = ServeEngine(model, params, CTX, num_slots=1, max_len=32)
    with pytest.raises(ValueError, match="own engine"):
        Router([eng, eng])
    with pytest.raises(ValueError, match="seed"):
        Router([eng, ServeEngine(model, params, CTX, num_slots=1,
                                 max_len=32, seed=1)])
    with pytest.raises(ValueError, match="at least one"):
        Router([])


def test_router_rejects_duplicate_rid():
    cfg, model, params = _bundle("gemma-7b")
    router = Router([ServeEngine(model, params, CTX, num_slots=1,
                                 max_len=32)])
    p = _prompts(cfg.vocab_size, (5,))[0]
    router.submit(Request(rid=0, prompt=p, max_new_tokens=2))
    with pytest.raises(ValueError, match="duplicate"):
        router.submit(Request(rid=0, prompt=p, max_new_tokens=2))


def test_validate_rejects_divergent_plans():
    """ZS-L009 at construction: replicas running different plans would
    produce placement-dependent tokens."""
    cfg, model, params = _bundle("gemma-7b")
    engines = [ServeEngine(model, params, CTX, num_slots=1, max_len=32,
                           plan=Plan(backend="jnp")),
               ServeEngine(model, params, CTX, num_slots=1, max_len=32,
                           plan=Plan(backend="interpret"))]
    with pytest.raises(ValueError, match="ZS-L009"):
        Router(engines, validate=True)


def test_validate_rejects_unbounded_requeue_backoff():
    """ZS-F004 at construction: the policy's worst-case total backoff
    must stay below the request timeout."""
    cfg, model, params = _bundle("gemma-7b")
    engines = [ServeEngine(model, params, CTX, num_slots=1, max_len=32)]
    with pytest.raises(ValueError, match="ZS-F004"):
        Router(engines, validate=True,
               policy=RetryPolicy(max_retries=3, backoff_base_s=10.0,
                                  restart_on_exhaustion=False),
               request_timeout_s=5.0)
    # the same fleet with a sane budget constructs fine
    Router(engines, validate=True,
           policy=RetryPolicy(max_retries=3, backoff_base_s=0.1,
                              restart_on_exhaustion=False),
           request_timeout_s=5.0)


# ----------------------------------------------------------------------
# ShardedEngine
# ----------------------------------------------------------------------
def test_sharded_engine_single_device_parity():
    from repro.launch.mesh import make_mesh
    cfg, model, params = _bundle("gemma-7b")
    lens, max_new = (5, 11, 3, 8), (6, 3, 5, 7)
    baseline = ServeEngine(model, params, CTX, num_slots=2,
                           max_len=32).run(_requests(cfg, lens, max_new))
    mesh = make_mesh((1, 1), ("data", "model"))
    sharded = ShardedEngine(model, params, CTX, mesh=mesh, num_slots=2,
                            max_len=32)
    results = sharded.run(_requests(cfg, lens, max_new))
    for i in range(4):
        assert results[i].tokens == baseline[i].tokens


def test_sharded_engine_rejects_paged_cache():
    from repro.launch.mesh import make_mesh
    cfg, model, params = _bundle("gemma-7b")
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="page_size"):
        ShardedEngine(model, params, CTX, mesh=mesh, max_len=32,
                      page_size=4)


def test_sharded_engine_8_device_parity():
    """Subprocess (XLA locks the device count at first init): on a
    (1, 8) CPU mesh the sharded engine must shard params for real and
    still match the unsharded engine token-for-token."""
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import Ctx, build_model
        from repro.launch.mesh import make_mesh
        from repro.serve import Request, ServeEngine
        from repro.serve.cluster import ShardedEngine

        assert jax.device_count() == 8
        cfg = get_config("gemma-7b", reduced=True)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
        ctx = Ctx(plan="jnp", dtype=jnp.float32)
        prompts = [list(np.random.default_rng(i).integers(
            0, cfg.vocab_size, n)) for i, n in enumerate((5, 11, 3, 8))]
        reqs = lambda: [Request(rid=i, prompt=p, max_new_tokens=m)
                        for i, (p, m) in enumerate(zip(prompts,
                                                       (6, 3, 5, 7)))]
        base = ServeEngine(model, params, ctx, num_slots=2,
                           max_len=32, steps_per_dispatch=4).run(reqs())
        mesh = make_mesh((1, 8), ("data", "model"))
        eng = ShardedEngine(model, params, ctx, mesh=mesh, num_slots=2,
                            max_len=32, steps_per_dispatch=4)
        sharded_leaves = sum(
            not leaf.sharding.is_fully_replicated
            for leaf in jax.tree.leaves(eng.params))
        assert sharded_leaves > 0, "no param leaf actually sharded"
        res = eng.run(reqs())
        for i in range(4):
            assert res[i].tokens == base[i].tokens, i
        print("SHARDED_LEAVES", sharded_leaves)
        print("OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=520,
                         env=env)
    assert out.returncode == 0, f"STDOUT:{out.stdout}\nSTDERR:{out.stderr}"
    assert "OK" in out.stdout


def test_kernels_on_4_device_mesh_and_per_device_replicas():
    """Subprocess on 4 forced CPU devices, interpret-mode kernels.

    Mosaic kernels cannot be partitioned by GSPMD, so under a mesh the
    linears run Megatron splits and prefill attention runs
    head-parallel through shard_map: ShardedEngine's prefill and decode
    logits must match the unsharded engine's.  Router replicas built
    with one device each must hold params and cache on that device and
    still emit the single engine's tokens."""
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import Ctx, build_model
        from repro.launch.mesh import make_mesh
        from repro.plan import KernelConfig
        from repro.serve import Request, Router, ServeEngine
        from repro.serve.cluster import ShardedEngine

        devices = jax.devices()
        assert len(devices) == 4
        cfg = get_config("deepseek-coder-33b", reduced=True)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
        ctx = Ctx(plan=KernelConfig(backend="interpret"), dtype=jnp.float32)
        prompts = [list(np.random.default_rng(i).integers(
            0, cfg.vocab_size, n)) for i, n in enumerate((5, 11, 3, 8))]
        reqs = lambda: [Request(rid=i, prompt=p, max_new_tokens=4)
                        for i, p in enumerate(prompts)]
        kw = dict(num_slots=2, max_len=32, steps_per_dispatch=2)
        single = ServeEngine(model, params, ctx, **kw)
        base = single.run(reqs())

        sharded = ShardedEngine(model, params, ctx,
                                mesh=make_mesh((1, 4), ("data", "model")),
                                **kw)
        batch = {"tokens": jnp.asarray([prompts[1] + [0] * 5], jnp.int32),
                 "lengths": jnp.asarray([11], jnp.int32)}
        out = {}
        for name, eng in (("single", single), ("sharded", sharded)):
            lg, cache = eng._prefill(eng.params, batch)
            tok = jnp.asarray([[7]], jnp.int32)
            dl, _ = jax.jit(lambda p, c, t, e=eng: model.decode(
                p, c, t, e.ctx))(eng.params, cache, tok)
            out[name] = (np.asarray(lg), np.asarray(dl))
        for a, b in zip(out["sharded"], out["single"]):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)

        engines = [ServeEngine(model, params, ctx, device=d, **kw)
                   for d in devices]
        for eng, d in zip(engines, devices):
            assert jax.tree.leaves(eng.params)[0].devices() == {d}
            assert eng.cache["k"].devices() == {d}
        routed = Router(engines).run(reqs())
        assert len({r.replica for r in routed.values()}) > 1
        for i in range(4):
            assert routed[i].tokens == base[i].tokens, i
        print("OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=520,
                         env=env)
    assert out.returncode == 0, f"STDOUT:{out.stdout}\nSTDERR:{out.stderr}"
    assert "OK" in out.stdout
