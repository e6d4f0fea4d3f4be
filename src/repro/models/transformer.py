"""Dense decoder-only LM with scan-over-layers and KV-cache decode.

Shared by the dense / vlm / moe families (moe swaps the MLP).  Layers
are stacked along a leading axis and executed with `jax.lax.scan`, so
HLO size and compile time are O(1) in depth — required to lower the
88-layer mistral-large-123b in this container (DESIGN.md §7.3).

How a layer's weights reach the kernels: training (`forward`,
`loss_fn`) scans over the stacked parameters as its ``xs``, which
slices every weight out of its stack each iteration.  The serving
scans (`prefill`, `prefill_chunk`, `decode_step`) scan over the layer
index instead, with the stacks closed over: each linear weight stays
in its (L, K, N) stack and ``ops.matmul(..., layer=i)`` lets the
zero-stall kernel DMA layer i's tiles straight out of it, where the
kernel runs and its tiles divide the weight.  Otherwise — norm scales,
quantized weights, MoE experts, a mesh, the jnp backend, tiles that
would pad the weight — the layer is sliced as the ``xs`` scan slices
it, with the same result.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.layers import Ctx, Params

__all__ = ["init_params", "forward", "loss_fn", "init_cache", "prefill",
           "prefill_chunk", "decode_step", "remat_policy"]


def remat_policy(cfg: ModelConfig):
    if cfg.remat == "none":
        return None
    if cfg.remat == "full":
        return jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint_policies.dots_with_no_batch_dims_saveable


# ----------------------------------------------------------------------
# init
# ----------------------------------------------------------------------
def _init_layer(key, cfg: ModelConfig, dtype,
                init_mlp_fn: Callable | None = None) -> Params:
    k1, k2 = jax.random.split(key)
    mlp_init = init_mlp_fn or (lambda k: L.init_mlp(k, cfg, dtype))
    return {
        "attn_norm": L.init_rmsnorm(cfg.d_model, dtype),
        "attn": L.init_attention(k1, cfg, dtype),
        "mlp_norm": L.init_rmsnorm(cfg.d_model, dtype),
        "mlp": mlp_init(k2),
    }


def init_params(key, cfg: ModelConfig, dtype=jnp.float32,
                init_mlp_fn: Callable | None = None) -> Params:
    ke, kl = jax.random.split(key)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    stacked = jax.vmap(
        lambda k: _init_layer(k, cfg, dtype, init_mlp_fn))(layer_keys)
    return {
        "embed": L.init_embed(ke, cfg, dtype),
        "layers": stacked,
        "final_norm": L.init_rmsnorm(cfg.d_model, dtype),
    }


# ----------------------------------------------------------------------
# forward (train / prefill)
# ----------------------------------------------------------------------
def _layer_fwd(cfg: ModelConfig, ctx: Ctx, mlp_fn: Callable | None,
               x: jax.Array, lp: Params, positions: jax.Array
               ) -> tuple[jax.Array, jax.Array]:
    """One block.  mlp_fn protocol: (params, x) -> (y, aux_loss)."""
    x = L.shard_act(x, ctx)   # SP: sequence-sharded residual (DESIGN.md §4)
    h = L.rms_norm(lp["attn_norm"], x, cfg.norm_eps)
    # output-side SP constraints make GSPMD emit the reduce-scatter
    # (not all-reduce+slice) form at the TP boundaries — L.shard_seq.
    # NOT applied around MoE blocks: it fights the EP dispatch layout
    # (measured olmoe train collective 25 s -> 68 s; perf_log.md).
    x = x + L.shard_seq(
        L.attention(lp["attn"], h, cfg, ctx, positions=positions), ctx)
    h = L.rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
    if mlp_fn is None:
        y = L.shard_seq(L.mlp(lp["mlp"], h, cfg, ctx), ctx)
        aux = jnp.zeros((), jnp.float32)
    else:
        y, aux = mlp_fn(lp["mlp"], h)
    return x + y, aux


def forward(params: Params, tokens: jax.Array, cfg: ModelConfig, ctx: Ctx,
            *, frontend_embeds: jax.Array | None = None,
            mlp_fn: Callable | None = None,
            return_aux: bool = False,
            last_only: bool = False):
    """tokens: (B, S_text) -> logits (B, S_text, V).

    For vlm/audio families, `frontend_embeds` (B, P, d) are prepended;
    logits are returned for text positions only.  With return_aux, also
    returns the mean per-layer auxiliary loss (MoE load balancing).
    """
    x = L.embed(params["embed"], tokens, ctx)
    n_front = 0
    if frontend_embeds is not None:
        n_front = frontend_embeds.shape[1]
        x = jnp.concatenate([frontend_embeds.astype(ctx.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

    body = functools.partial(_layer_fwd, cfg, ctx, mlp_fn)
    policy = remat_policy(cfg)
    if policy is not None:
        body = jax.checkpoint(body, policy=policy)

    def scan_body(x, lp):
        x, aux = body(x, lp, positions)
        return x, aux

    x, auxes = jax.lax.scan(scan_body, x, params["layers"])
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    if n_front:
        x = x[:, n_front:]
    if last_only:   # serving prefill: only the next-token logits
        x = x[:, -1:]
    logits = L.unembed(params["embed"], x, ctx)
    if return_aux:
        return logits, jnp.mean(auxes)
    return logits


def loss_fn(params: Params, batch: dict, cfg: ModelConfig, ctx: Ctx,
            *, mlp_fn: Callable | None = None,
            aux_coef: float = 0.01) -> jax.Array:
    logits, aux = forward(params, batch["tokens"], cfg, ctx,
                          frontend_embeds=batch.get("frontend_embeds"),
                          mlp_fn=mlp_fn, return_aux=True)
    return L.cross_entropy(logits, batch["targets"]) + aux_coef * aux


# ----------------------------------------------------------------------
# decode
# ----------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, *, quantize_kv: bool = False) -> Params:
    """quantize_kv: int8 cache storage with per-(position, kv-head)
    scales — halves (vs bf16) the dominant decode memory term and
    capacity (EXPERIMENTS.md §Perf: qwen decode_32k carries 5.5 TB of
    global MHA KV at 128x32k).  Dequantization happens per layer inside
    the score/PV dots (fused on TPU)."""
    hd = cfg.resolved_head_dim
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, hd)
    if quantize_kv:
        sshape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, 1)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, dtype),
                "v_scale": jnp.zeros(sshape, dtype),
                "pos": jnp.zeros((), jnp.int32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "pos": jnp.zeros((), jnp.int32)}


def _layer_decode(cfg: ModelConfig, ctx: Ctx, mlp_fn: Callable | None,
                  x: jax.Array, lp: Params, layer_cache: Params,
                  pos: jax.Array, page_table: jax.Array | None = None
                  ) -> tuple[jax.Array, Params]:
    h = L.rms_norm(lp["attn_norm"], x, cfg.norm_eps)
    if page_table is not None:
        a, new_cache = L.attention_decode_paged(
            lp["attn"], h, cfg, ctx, cache=layer_cache,
            page_table=page_table, pos=pos)
    elif "k_scale" in layer_cache:
        a, new_cache = L.attention_decode_quantized(
            lp["attn"], h, cfg, ctx, cache=layer_cache, pos=pos)
    else:
        a, new_cache = L.attention_decode(lp["attn"], h, cfg, ctx,
                                          cache=layer_cache, pos=pos)
    x = x + a
    h = L.rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
    fn = mlp_fn or (lambda p, v: (L.mlp(p, v, cfg, ctx), 0.0))
    y, _ = fn(lp["mlp"], h)
    return x + y, new_cache


def _layer_ids(layers: Params) -> jax.Array:
    """0..L-1, the serving scans' ``xs`` in place of the weights."""
    return jnp.arange(jax.tree.leaves(layers)[0].shape[0], dtype=jnp.int32)


def _layer_at(layers: Params, ctx: Ctx, mlp_fn: Callable | None):
    """Layer i's parameters, the weight stacks closed over rather than
    scanned (:func:`repro.models.layers.layer_params`).  A custom
    ``mlp_fn`` (MoE experts) gets its subtree sliced."""
    sliced = ("mlp",) if mlp_fn is not None else ()
    return lambda i: L.layer_params(layers, i, ctx, sliced=sliced)


def decode_step(params: Params, cache: Params, tokens: jax.Array,
                cfg: ModelConfig, ctx: Ctx,
                *, mlp_fn: Callable | None = None
                ) -> tuple[jax.Array, Params]:
    """tokens: (B, 1) -> (logits (B, 1, V), updated cache).

    A ``"page_table"`` leaf switches the attention path to the paged KV
    pool (:func:`repro.models.layers.attention_decode_paged`); the table
    rides outside the layer scan like ``"pos"`` and passes through
    unchanged (the engine rewrites it on admission/retire).
    """
    pos = cache["pos"]
    page_table = cache.get("page_table")
    x = L.embed(params["embed"], tokens, ctx)
    layer_at = _layer_at(params["layers"], ctx, mlp_fn)

    def scan_body(x, layer):
        i, lc = layer
        x, new_lc = _layer_decode(cfg, ctx, mlp_fn, x, layer_at(i), lc,
                                  pos, page_table)
        return x, new_lc

    lc = {k: v for k, v in cache.items()
          if k not in ("pos", "page_table")}
    x, new_kv = jax.lax.scan(scan_body, x,
                             (_layer_ids(params["layers"]), lc))
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed(params["embed"], x, ctx)
    out = {**new_kv, "pos": pos + 1}
    if page_table is not None:
        out["page_table"] = page_table
    return logits, out


def prefill(params: Params, tokens: jax.Array, cfg: ModelConfig, ctx: Ctx,
            max_len: int, *, mlp_fn: Callable | None = None,
            lengths: jax.Array | None = None,
            frontend_embeds: jax.Array | None = None
            ) -> tuple[jax.Array, Params]:
    """Run the prompt in ONE fused call: last-valid-position logits +
    populated KV cache, i.e. prompt ingestion without `prompt_len`
    decode dispatches.

    ``lengths``: optional (B,) valid prompt lengths for ragged batches
    (continuous-batching admission) — attention is masked per sequence,
    each row's logits are taken at its own last valid position, and
    the returned ``cache["pos"]`` is the (B,) per-slot write position.
    Without ``lengths`` the historical uniform behavior is kept
    (scalar ``pos``).  ``frontend_embeds`` (B, P, d) are prepended
    (vlm/audio families); their P positions count toward the cache.
    """
    x = L.embed(params["embed"], tokens, ctx)
    n_front = 0
    if frontend_embeds is not None:
        n_front = frontend_embeds.shape[1]
        x = jnp.concatenate([frontend_embeds.astype(ctx.dtype), x], axis=1)
    B, S, _ = x.shape
    if S > max_len:
        raise ValueError(f"prompt length {S} exceeds max_len {max_len}")
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    hd = cfg.resolved_head_dim
    lens = None if lengths is None else (
        jnp.asarray(lengths, jnp.int32) + n_front)
    layer_at = _layer_at(params["layers"], ctx, mlp_fn)

    def scan_body(x, i):
        lp = layer_at(i)
        h = L.rms_norm(lp["attn_norm"], x, cfg.norm_eps)
        q, k, v = L._qkv(lp["attn"], h, cfg, ctx)
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
        o = L._gqa_full(q, k, v, causal=True,
                        impl=L.ops.resolve_impl(ctx.plan.backend), ctx=ctx,
                        config=ctx.plan, lengths=lens, mesh=ctx.mesh)
        x = x + L.linear(lp["attn"]["wo"],
                         o.reshape(B, S, cfg.n_heads * hd), ctx,
                         split="row")
        h = L.rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
        fn = mlp_fn or (lambda p, v_: (L.mlp(p, v_, cfg, ctx), 0.0))
        y, _ = fn(lp["mlp"], h)
        x = x + y
        return x, {"k": k, "v": v}

    x, kv = jax.lax.scan(scan_body, x, _layer_ids(params["layers"]))
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    if lens is None:
        x_last = x[:, -1:]
        pos = jnp.asarray(S, jnp.int32)
    else:
        x_last = L.gather_last(x, lens)
        pos = lens
    logits = L.unembed(params["embed"], x_last, ctx)

    pad = max_len - S
    cache = {
        "k": jnp.pad(kv["k"], ((0, 0), (0, 0), (0, pad), (0, 0),
                               (0, 0))).astype(ctx.dtype),
        "v": jnp.pad(kv["v"], ((0, 0), (0, 0), (0, pad), (0, 0),
                               (0, 0))).astype(ctx.dtype),
        "pos": pos,
    }
    return logits, cache


def prefill_chunk(params: Params, tokens: jax.Array, cfg: ModelConfig,
                  ctx: Ctx, *, cache: Params, offset: jax.Array,
                  lengths: jax.Array, mlp_fn: Callable | None = None
                  ) -> tuple[jax.Array, Params]:
    """Process one chunk of a long prompt against a KV stripe in place.

    The anti-head-of-line half of paged serving: instead of one fused
    :func:`prefill` that stalls admission for everyone behind a long
    prompt, the engine feeds the prompt through this in fixed-size
    chunks *between* decode dispatches.  tokens: (B, C) — the chunk,
    zero-padded on the last call; cache: a contiguous
    ``init_cache(B, max_len)`` stripe (the engine pages it on final
    insertion); ``offset``: scalar absolute position of chunk row 0;
    ``lengths``: (B,) absolute valid end after this chunk
    (``<= offset + C``; strictly less only on the final, padded chunk).

    Each chunk's K/V are written into the stripe at ``offset`` and its
    queries attend to the whole stripe with ``q_offsets`` shifting the
    causal frontier — the same absolute-position masking the flash
    kernel already does for ragged batches, so chunked prefill stays on
    the Pallas path.  Returns per-row logits at ``lengths - 1`` (only
    meaningful on the final chunk) and the updated stripe with
    ``pos = lengths``, exactly the contract of :func:`prefill`.
    """
    x = L.embed(params["embed"], tokens, ctx)
    B, C, _ = x.shape
    offset = jnp.asarray(offset, jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    off_b = jnp.broadcast_to(offset, (B,))
    positions = off_b[:, None] + jnp.arange(C)[None, :]
    hd = cfg.resolved_head_dim
    zero = jnp.zeros((), jnp.int32)
    layer_at = _layer_at(params["layers"], ctx, mlp_fn)

    def scan_body(x, layer):
        i, lc = layer
        lp = layer_at(i)
        h = L.rms_norm(lp["attn_norm"], x, cfg.norm_eps)
        q, k, v = L._qkv(lp["attn"], h, cfg, ctx)
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
        ck = jax.lax.dynamic_update_slice(
            lc["k"], k.astype(lc["k"].dtype), (zero, offset, zero, zero))
        cv = jax.lax.dynamic_update_slice(
            lc["v"], v.astype(lc["v"].dtype), (zero, offset, zero, zero))
        o = L._gqa_full(q, ck, cv, causal=True,
                        impl=L.ops.resolve_impl(ctx.plan.backend), ctx=ctx,
                        config=ctx.plan, lengths=lens, q_offset=off_b,
                        mesh=ctx.mesh)
        x = x + L.linear(lp["attn"]["wo"],
                         o.reshape(B, C, cfg.n_heads * hd), ctx,
                         split="row")
        h = L.rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
        fn = mlp_fn or (lambda p, v_: (L.mlp(p, v_, cfg, ctx), 0.0))
        y, _ = fn(lp["mlp"], h)
        return x + y, {"k": ck, "v": cv}

    lc = {k: v for k, v in cache.items() if k != "pos"}
    x, new_kv = jax.lax.scan(scan_body, x,
                             (_layer_ids(params["layers"]), lc))
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    x_last = L.gather_last(x, lens - off_b)
    logits = L.unembed(params["embed"], x_last, ctx)
    return logits, {**new_kv, "pos": lens}
