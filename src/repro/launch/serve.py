"""Serving driver: continuous batching + fused prefill.

A production-shaped server loop over :class:`repro.serve.ServeEngine`:
requests arrive with (possibly mixed-length) prompts, are prefilled in
ONE fused ``Model.prefill`` call each, and decode in a continuously
re-filled slot pool — a finished request's slot is handed to the next
queued request on the following step.  Greedy sampling; on TPU the
matmul path is the zero-stall Pallas engine and ragged lengths stay on
the masked flash-attention kernel.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-7b --reduced \
      --batch 8 --num-slots 4 --prompt-len 32 --gen-len 32 --mixed

``--no-reduced`` serves the published widths.  The backend defaults to
``auto`` (the Pallas kernels on a TPU, the jnp reference elsewhere),
and params and compute default to bfloat16.  With ``--replicas N`` each
replica engine lives on its own device (round-robin over
``jax.devices()``).
"""

from __future__ import annotations

import argparse
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import Ctx, build_model
from repro.serve import Request, Router, ServeEngine, ShardedEngine

__all__ = ["serve_batch"]


def _parse_mesh(spec: str):
    """``"DxM"`` -> a ('data', 'model') mesh over the local devices."""
    from repro.launch.mesh import make_mesh
    try:
        d, m = (int(x) for x in spec.lower().split("x"))
    except ValueError:
        raise ValueError(f"--mesh must look like 'DxM' (e.g. 1x8), "
                         f"got {spec!r}") from None
    return make_mesh((d, m), ("data", "model"))


def _make_requests(cfg, key, batch: int, prompt_len: int, gen_len: int,
                   mixed: bool, *, temperature: float = 0.0,
                   top_k: int = 0, top_p: float = 1.0):
    """`batch` requests; with `mixed`, prompt lengths cycle through
    {prompt_len, prompt_len/2, prompt_len/4, 3*prompt_len/4} — the
    ragged traffic shape continuous batching exists for."""
    toks = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab_size)
    toks = np.asarray(toks)
    reqs = []
    for i in range(batch):
        if mixed:
            frac = (1.0, 0.5, 0.25, 0.75)[i % 4]
            n = max(1, int(prompt_len * frac))
        else:
            n = prompt_len
        extra = None
        if cfg.family == "encdec" or cfg.frontend:
            d = cfg.d_model
            p = prompt_len if cfg.family == "encdec" else cfg.frontend_tokens
            extra = np.asarray(
                jax.random.normal(jax.random.fold_in(key, i), (p, d)) * 0.1)
        reqs.append(Request(rid=i, prompt=toks[i, :n].tolist(),
                            max_new_tokens=gen_len, frontend_embeds=extra,
                            temperature=temperature, top_k=top_k,
                            top_p=top_p))
    return reqs


def serve_batch(arch: str, *, reduced: bool = True, batch: int = 4,
                prompt_len: int = 32, gen_len: int = 32, seed: int = 0,
                dtype=jnp.bfloat16, num_slots: int | None = None,
                mixed: bool = False, impl: str = "auto",
                steps_per_dispatch: int = 1, temperature: float = 0.0,
                top_k: int = 0, top_p: float = 1.0,
                plan=None, plan_out: str | None = None,
                validate_plan: bool = False,
                step_timeout_s: float | None = None,
                page_size: int | None = None,
                num_pages: int | None = None,
                prefill_chunk: int | None = None,
                replicas: int = 1, mesh: str | None = None,
                kill_replica: int | None = None,
                kill_at_step: int = 2) -> dict:
    """Run a synthetic request batch through the serving engine.

    ``impl`` is the backend; ``dtype`` is both the params' storage
    and the compute dtype (weights are built in it by a jitted init,
    with no float32 copy of a whole layer stack); ``plan`` is forwarded
    to
    :class:`~repro.serve.ServeEngine` (a :class:`repro.plan.Plan`, a
    path to a saved plan JSON, or ``"trace"`` to resolve every kernel
    config ahead of time); ``plan_out`` saves the engine's active plan
    afterwards — the execution schedule as a shippable artifact;
    ``validate_plan`` runs the static analyzer over the active plan at
    engine construction (``ServeEngine(validate=True)``), rejecting a
    hazardous shipped plan before it serves.
    ``steps_per_dispatch`` fuses K decode+sample iterations into one
    jitted dispatch (one host sync per block); ``temperature`` /
    ``top_k`` / ``top_p`` select on-device sampling (0/0/1.0 = exact
    greedy), seeded per request from ``seed``.
    ``page_size`` switches the KV cache to the paged pool
    (:mod:`repro.serve.paging`; must divide ``prompt_len + gen_len``),
    ``num_pages`` sizes the pool (default: no oversubscription), and
    ``prefill_chunk`` ingests long prompts chunk-by-chunk between
    decode dispatches.

    Cluster knobs (:mod:`repro.serve.cluster`): ``replicas`` fronts N
    data-parallel engine replicas with a :class:`repro.serve.Router`
    (load-aware placement, fault-tolerant re-queue), replica ``i`` on
    device ``i % jax.device_count()``; ``mesh`` (e.g.
    ``"1x8"``) runs each engine as a :class:`repro.serve.ShardedEngine`
    over a ('data', 'model') device mesh (model-parallel decode);
    ``kill_replica`` fails that replica at router step
    ``kill_at_step`` — the CI smoke's fault injection, proving its
    in-flight requests finish on survivors.
    """
    from repro.plan import Plan
    cfg = get_config(arch, reduced=reduced)
    model = build_model(cfg)
    ctx = Ctx(plan=impl, dtype=dtype)
    key = jax.random.PRNGKey(seed)
    params = jax.jit(model.init, static_argnames="dtype")(key, dtype=dtype)

    if isinstance(plan, str) and plan != "trace":
        plan = Plan.load(plan)
    if isinstance(plan, Plan) and plan.backend != impl:
        warnings.warn(
            f"serve: the loaded plan's backend {plan.backend!r} overrides "
            f"impl={impl!r} — the engine executes under the plan's backend",
            RuntimeWarning, stacklevel=2)
    slots = num_slots or min(batch, 4)
    frontier = prompt_len + (cfg.frontend_tokens if cfg.frontend else 0)
    max_len = frontier + gen_len
    cache_kwargs = {"enc_len": prompt_len} if cfg.family == "encdec" else None
    device_mesh = _parse_mesh(mesh) if mesh is not None else None

    def make_engine(device=None):
        kw = dict(num_slots=slots, max_len=max_len, cache_dtype=dtype,
                  steps_per_dispatch=steps_per_dispatch, seed=seed,
                  cache_kwargs=cache_kwargs, plan=plan,
                  validate=validate_plan, page_size=page_size,
                  num_pages=num_pages, prefill_chunk=prefill_chunk)
        if device_mesh is not None:
            return ShardedEngine(model, params, ctx, mesh=device_mesh, **kw)
        return ServeEngine(model, params, ctx, device=device, **kw)

    reqs = _make_requests(cfg, key, batch, prompt_len, gen_len, mixed,
                          temperature=temperature, top_k=top_k, top_p=top_p)
    cluster: dict | None = None
    if replicas > 1:
        devices = jax.devices()
        engines = [make_engine(None if device_mesh is not None
                               else devices[i % len(devices)])
                   for i in range(replicas)]
        router = Router(engines, validate=validate_plan,
                        step_timeout_s=step_timeout_s)
        for r in reqs:
            router.submit(r)
        step = 0
        while not router.idle:
            if kill_replica is not None and step == kill_at_step:
                router.kill(kill_replica)
            router.step()
            step += 1
        if kill_replica is not None and router.deaths == 0:
            raise RuntimeError(
                f"kill_replica={kill_replica} never fired: the run "
                f"finished in {step} steps (<= kill_at_step="
                f"{kill_at_step}) — the fault-injection smoke was "
                f"vacuous; raise --gen-len or lower --kill-at-step")
        results = router.results
        fleet = router.stats()
        snap = router.snapshot()
        cluster = snap["router"]
        cluster["per_replica_dispatches"] = [
            r["dispatches"] for r in snap["per_replica"]]
        active_plan, stats_snap = engines[0].plan, snap
        tp = {"prefill_tok_s": fleet.prefill_tok_s,
              "decode_tok_s": fleet.decode_tok_s,
              "prefill_s": fleet.prefill_s, "decode_s": fleet.decode_s}
    else:
        engine = make_engine()
        results = engine.run(reqs, step_timeout_s=step_timeout_s)
        active_plan, stats_snap = engine.plan, engine.stats.snapshot()
        tp = engine.throughput()
    if plan_out:
        active_plan.save(plan_out)

    gen = np.full((batch, gen_len), -1, np.int64)
    for rid, res in results.items():
        gen[rid, :len(res.tokens)] = res.tokens
    out = {
        "generated": jnp.asarray(gen),
        "prefill_s": tp["prefill_s"],
        "decode_s": tp["decode_s"],
        "prefill_tok_s": tp["prefill_tok_s"],
        "decode_tok_s": tp["decode_tok_s"],
        # back-compat blended name == decode throughput (prefill is
        # reported separately; the old metric ignored it entirely)
        "tokens_per_s": tp["decode_tok_s"],
        # full EngineStats snapshot: the legacy aggregate keys plus
        # derived throughput, occupancy, and latency summaries (the
        # fleet aggregate + per-replica/router sections when routed)
        "stats": stats_snap,
    }
    if cluster is not None:
        out["cluster"] = cluster
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-7b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="the config's smoke-test widths (--no-reduced: "
                         "the published widths)")
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["bfloat16", "float32"],
                    help="params and compute dtype")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--num-slots", type=int, default=None)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--mixed", action="store_true",
                    help="mixed prompt lengths (ragged traffic)")
    ap.add_argument("--impl", default="auto",
                    choices=["auto", "jnp", "pallas", "interpret"],
                    help="kernel backend (auto: Pallas on a TPU, the jnp "
                         "reference elsewhere)")
    ap.add_argument("--steps-per-dispatch", type=int, default=1,
                    help="decode+sample iterations fused into one jitted "
                         "dispatch (one host sync per block)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k sampling cutoff (0 = disabled)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = disabled)")
    ap.add_argument("--seed", type=int, default=0,
                    help="engine sampling seed (per-request chains are "
                         "folded in from it)")
    ap.add_argument("--plan", default=None,
                    help="'trace' to resolve all kernel configs ahead of "
                         "time, or a path to a saved plan JSON")
    ap.add_argument("--plan-out", default=None,
                    help="save the engine's active execution plan here")
    ap.add_argument("--validate-plan", action="store_true",
                    help="statically verify the active plan at load time "
                         "(repro.analyze.lint_plan); error diagnostics "
                         "abort before serving")
    ap.add_argument("--page-size", type=int, default=None,
                    help="tokens per KV page; switches the cache to the "
                         "paged pool with refcounted prefix sharing "
                         "(default: contiguous per-slot cache)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="physical page-pool size incl. the trash page "
                         "(default: num_slots tables, no oversubscription)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="ingest prompts longer than this in fixed-size "
                         "chunks between decode dispatches (bounds the "
                         "head-of-line TTFT of long prompts)")
    ap.add_argument("--step-timeout", type=float, default=None,
                    help="fail if any engine step exceeds this many seconds")
    ap.add_argument("--replicas", type=int, default=1,
                    help="front N data-parallel engine replicas with the "
                         "cluster Router (load-aware placement, "
                         "fault-tolerant re-queue)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="run each engine model-parallel over a "
                         "('data','model') device mesh, e.g. 1x8 (pair "
                         "with XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=8 on CPU)")
    ap.add_argument("--kill-replica", type=int, default=None,
                    help="fail this replica mid-run (fault-injection "
                         "smoke; its in-flight requests re-queue onto "
                         "survivors)")
    ap.add_argument("--kill-at-step", type=int, default=2,
                    help="router step at which --kill-replica fires")
    ap.add_argument("--metrics", action="store_true",
                    help="print per-request latency percentiles (TTFT, "
                         "queue wait, per-token p50/p99) and the per-op "
                         "utilization table after the run")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a JSONL span/event trace of the run here "
                         "(implies tracing on; one JSON object per line)")
    args = ap.parse_args()
    use_compile_cache()

    # --trace-out / --metrics turn observability on for the run:
    # spans/events stream to the JSONL sink (if any), kernel dispatches
    # feed the utilization table
    if args.trace_out:
        obs.enable(trace_path=args.trace_out)
    elif args.metrics:
        obs.enable()
    try:
        out = serve_batch(args.arch, reduced=args.reduced, batch=args.batch,
                          dtype=jnp.dtype(args.dtype),
                          prompt_len=args.prompt_len, gen_len=args.gen_len,
                          num_slots=args.num_slots, mixed=args.mixed,
                          impl=args.impl, seed=args.seed,
                          steps_per_dispatch=args.steps_per_dispatch,
                          temperature=args.temperature, top_k=args.top_k,
                          top_p=args.top_p,
                          plan=args.plan, plan_out=args.plan_out,
                          validate_plan=args.validate_plan,
                          step_timeout_s=args.step_timeout,
                          page_size=args.page_size,
                          num_pages=args.num_pages,
                          prefill_chunk=args.prefill_chunk,
                          replicas=args.replicas, mesh=args.mesh,
                          kill_replica=args.kill_replica,
                          kill_at_step=args.kill_at_step)
        s = out["stats"]
        print(f"generated shape: {out['generated'].shape}")
        print(f"prefill: {out['prefill_s']:.2f}s "
              f"({out['prefill_tok_s']:.1f} tok/s)  "
              f"decode: {out['decode_s']:.2f}s "
              f"({out['decode_tok_s']:.1f} tok/s)")
        print(f"steps: {s['decode_steps']}  dispatches: {s['dispatches']}  "
              f"admitted: {s['admitted']}  retired: {s['retired']}  "
              f"max concurrent: {s['max_concurrent']}")
        if args.page_size is not None or args.prefill_chunk is not None:
            print(f"pages in use (peak): {s['pages_in_use']}  "
                  f"shared: {s['pages_shared']}  "
                  f"prefill chunks: {s['prefill_chunks']}")
        if "cluster" in out:
            c = out["cluster"]
            print(f"cluster: replicas: {c['replicas']}  "
                  f"alive: {c['alive']}  deaths: {c['deaths']}  "
                  f"requeues: {c['requeues']}  "
                  f"per-replica dispatches: "
                  f"{c['per_replica_dispatches']}")
        if args.metrics:
            for name in ("ttft", "queue_wait", "token_latency"):
                m = s[name]
                print(f"{name}: p50={m['p50']:.4f}s p99={m['p99']:.4f}s "
                      f"max={m['max']:.4f}s (n={m['n']})")
            print(f"mean dispatch occupancy: "
                  f"{s['mean_dispatch_occupancy']:.2f}")
            print("op,M,N,K,dtype,backend,config,count,predicted_util")
            for r in obs.utilization_table():
                print(f"{r['op']},{r['M']},{r['N']},{r['K']},{r['dtype']},"
                      f"{r['backend']},{r['config']},{r['count']},"
                      f"{r['predicted_util']:.4f}")
        if args.trace_out:
            print(f"trace written to {args.trace_out}")
    finally:
        if args.trace_out or args.metrics:
            obs.reset_records()
            obs.disable()


if __name__ == "__main__":
    main()
