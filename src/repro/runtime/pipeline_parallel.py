"""GPipe-style pipeline parallelism over the 'pod' mesh axis.

The multi-pod mesh's 'pod' axis has the slowest links (DCN), which is
exactly where pipeline parallelism beats data parallelism: per tick
only one microbatch activation crosses the pod boundary
(`collective_permute`) instead of every gradient.

Schedule: classic GPipe — P stages, M microbatches, M+P-1 ticks; stage
p processes microbatch (t - p) at tick t.  The whole rotation lives
inside one `shard_map` over 'pod', with activations handed to the next
stage by `jax.lax.ppermute`.  Backward flows through the transposed
permutes automatically under `jax.grad` (full-forward-then-backward;
pair with remat for memory).

This module pipelines the *dense transformer* family (stage = a slab of
layers; embedding on stage 0, head+loss on the last stage) and is
validated for numerical parity against the non-PP loss in tests.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.layers import Ctx
from repro.models.transformer import _layer_fwd

__all__ = ["pp_loss_fn", "split_layers_for_stages"]


def split_layers_for_stages(params: Any, n_stages: int) -> Any:
    """Reshape stacked layer params (L, ...) -> (P, L/P, ...)."""
    def reshape(x):
        L_, rest = x.shape[0], x.shape[1:]
        assert L_ % n_stages == 0, f"{L_} layers not divisible by {n_stages}"
        return x.reshape(n_stages, L_ // n_stages, *rest)
    return jax.tree.map(reshape, params)


def pp_loss_fn(params: Any, batch: dict, cfg: ModelConfig, ctx: Ctx,
               mesh: Mesh, *, n_microbatches: int,
               axis: str = "pod") -> jax.Array:
    """Pipeline-parallel train loss for the dense family.

    params: {"embed","layers","final_norm"} with layers stacked (L,...).
    The layer stack is split across the `axis` mesh dimension; embed /
    final_norm / head run on first / last stage (their params are
    replicated — they are small relative to the stack).
    """
    n_stages = mesh.devices.shape[mesh.axis_names.index(axis)]
    staged = split_layers_for_stages(params["layers"], n_stages)
    tokens, targets = batch["tokens"], batch["targets"]
    B, S = tokens.shape
    M = n_microbatches
    assert B % M == 0, f"batch {B} not divisible by microbatches {M}"
    mb = B // M
    tok_mb = tokens.reshape(M, mb, S)
    tgt_mb = targets.reshape(M, mb, S)

    other_axes = [a for a in mesh.axis_names if a != axis]
    stage_spec = P(axis)      # leading stage dim of the layer stack
    repl = P()

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(stage_spec, repl, repl, repl),
        out_specs=repl,
        check_vma=False)
    def run(stage_layers, embed_p, final_norm_p, tok_tgt):
        tok_mb_, tgt_mb_ = tok_tgt
        p = jax.lax.axis_index(axis)
        n_p = jax.lax.axis_size(axis)
        stage_layers = jax.tree.map(lambda x: x[0], stage_layers)
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (mb, S))

        def stage_apply(x):
            def body(x, lp):
                x, _ = _layer_fwd(cfg, ctx, None, x, lp, positions)
                return x, None
            x, _ = jax.lax.scan(body, x, stage_layers)
            return x

        d = cfg.d_model
        zero_act = jnp.zeros((mb, S, d), ctx.dtype)
        perm = [(i, (i + 1) % n_p) for i in range(n_p)]

        def tick(t, carry):
            recv, loss_sum = carry
            mb_idx = t - p
            active = (mb_idx >= 0) & (mb_idx < M)
            idx0 = jnp.clip(t, 0, M - 1)
            x_first = L.embed(embed_p, tok_mb_[idx0], ctx)
            x_in = jnp.where(p == 0, x_first, recv)
            y = stage_apply(x_in)
            y = jnp.where(active, y, zero_act)
            # last stage: head + loss for its microbatch
            h = L.rms_norm(final_norm_p, y, cfg.norm_eps)
            logits = L.unembed({"tokens": embed_p["tokens"],
                                **({"lm_head": embed_p["lm_head"]}
                                   if "lm_head" in embed_p else {})},
                               h, ctx)
            idx_l = jnp.clip(t - (n_p - 1), 0, M - 1)
            mb_loss = L.cross_entropy(logits, tgt_mb_[idx_l])
            take = active & (p == n_p - 1)
            loss_sum = loss_sum + jnp.where(take, mb_loss, 0.0)
            recv = jax.lax.ppermute(y, axis, perm)
            return recv, loss_sum

        recv, loss_sum = jax.lax.fori_loop(
            0, M + n_p - 1, tick, (zero_act, jnp.zeros((), jnp.float32)))
        # only the last stage holds the loss; share it
        loss = jax.lax.psum(loss_sum, axis) / M
        for a in other_axes:
            loss = jax.lax.pmean(loss, a)
        return loss

    return run(staged, params["embed"], params["final_norm"],
               (tok_mb, tgt_mb))
