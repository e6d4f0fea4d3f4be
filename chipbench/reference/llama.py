"""Plain float32 reference of a Llama-style dense decoder, and the
benchmark's own weights for it.

Written from the published description (Llama / Mistral as in their
Hugging Face ``config.json``): RMSNorm with a learned scale, rotary
position embeddings on query and key (rotate-half form, base
``rope_theta``), grouped-query attention with causal softmax scaled by
``head_dim ** -0.5``, a SwiGLU MLP ``down(silu(gate(x)) * up(x))``,
untied embedding and LM head.  It imports nothing of the program under
test and takes nothing the program made: the weights are drawn here,
from the run's seed, and the reference draws them again itself, one
layer at a time, so that it fits on the chip beside nothing else.

Every matrix product runs in float32 at ``Precision.HIGHEST``.  The
control (``fp8=True``) rounds every weight matrix to the e4m3 grid
first, scaled to its own range: the step below the stated bfloat16.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration file."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    eps: float
    theta: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        if c.get("hidden_act") != "silu":
            raise ValueError(f"hidden_act {c.get('hidden_act')!r}: only silu")
        if c.get("rope_scaling") is not None:
            raise ValueError("rope_scaling is not part of this reference")
        if c.get("tie_word_embeddings"):
            raise ValueError("tied embeddings are not part of this reference")
        if c.get("sliding_window") is not None:
            raise ValueError("sliding-window attention is not part of this "
                             "reference")
        heads = int(c["num_attention_heads"])
        d = int(c["hidden_size"])
        return cls(layers=int(c["num_hidden_layers"]), d=d, heads=heads,
                   kv_heads=int(c["num_key_value_heads"]),
                   head_dim=int(c.get("head_dim") or d // heads),
                   ff=int(c["intermediate_size"]),
                   vocab=int(c["vocab_size"]),
                   eps=float(c["rms_norm_eps"]),
                   theta=float(c["rope_theta"]))


# ----------------------------------------------------------------------
# weights from the seed
# ----------------------------------------------------------------------
def root_key(seed: int) -> jax.Array:
    """A threefry key from any whole-number seed (2**31 and well past:
    ``PRNGKey`` keeps only 32 bits, so the seed is hashed first)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def _keys(key):
    k_outer, k_layers = jax.random.split(key)
    return k_outer, k_layers


def _linear(key, d_in, d_out, dtype):
    w = jax.random.normal(key, (d_in, d_out), jnp.float32) * d_in ** -0.5
    return w.astype(dtype)


def _norm(key, n, dtype):
    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)).astype(dtype)


def layer_weights(key, dims: Dims, dtype=jnp.bfloat16) -> dict:
    """One layer's weights, ``(d_in, d_out)`` per matrix."""
    d, hd = dims.d, dims.head_dim
    ks = jax.random.split(key, 9)
    return {
        "attn_norm": _norm(ks[0], d, dtype),
        "wq": _linear(ks[1], d, dims.heads * hd, dtype),
        "wk": _linear(ks[2], d, dims.kv_heads * hd, dtype),
        "wv": _linear(ks[3], d, dims.kv_heads * hd, dtype),
        "wo": _linear(ks[4], dims.heads * hd, d, dtype),
        "mlp_norm": _norm(ks[5], d, dtype),
        "w_gate": _linear(ks[6], d, dims.ff, dtype),
        "w_up": _linear(ks[7], d, dims.ff, dtype),
        "w_down": _linear(ks[8], dims.ff, d, dtype),
    }


def outer_weights(key, dims: Dims, dtype=jnp.bfloat16) -> dict:
    ks = jax.random.split(key, 3)
    embed = jax.random.normal(ks[0], (dims.vocab, dims.d), jnp.float32)
    return {"embed": embed.astype(dtype),
            "lm_head": _linear(ks[1], dims.d, dims.vocab, dtype),
            "final_norm": _norm(ks[2], dims.d, dtype)}


@functools.partial(jax.jit, static_argnums=0)
def _init(dims: Dims, key):
    k_outer, k_layers = _keys(key)
    layers = jax.lax.map(
        lambda i: layer_weights(jax.random.fold_in(k_layers, i), dims),
        jnp.arange(dims.layers))
    return {**outer_weights(k_outer, dims), "layers": layers}


def init_weights(dims: Dims, seed: int) -> dict:
    """Every weight in bfloat16, made on the device in one jitted call;
    ``layers`` holds each layer's matrices stacked on a leading axis."""
    return _init(dims, root_key(seed))


# ----------------------------------------------------------------------
# the forward pass
# ----------------------------------------------------------------------
def fp8_round(w: jax.Array) -> jax.Array:
    """Round a matrix to the e4m3 grid at a per-matrix scale (240: the
    largest finite e4m3 value ``reduce_precision`` keeps)."""
    w = w.astype(jnp.float32)
    if w.ndim < 2:
        return w
    s = 240.0 / jnp.maximum(jnp.max(jnp.abs(w)), 1e-30)
    return jax.lax.reduce_precision(w * s, exponent_bits=4,
                                    mantissa_bits=3) / s


def _f32(tree, fp8: bool):
    return jax.tree.map(fp8_round if fp8 else
                        (lambda w: w.astype(jnp.float32)), tree)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, pos, theta):
    """x: (S, heads, hd); rotate-half rotary embedding at ``pos``."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv            # (S, hd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def _layer(dims: Dims, x, w):
    S = x.shape[0]
    H, KV, hd = dims.heads, dims.kv_heads, dims.head_dim
    pos = jnp.arange(S)
    h = _rms(x, w["attn_norm"], dims.eps)
    q = _rope(_mm(h, w["wq"]).reshape(S, H, hd), pos, dims.theta)
    k = _rope(_mm(h, w["wk"]).reshape(S, KV, hd), pos, dims.theta)
    v = _mm(h, w["wv"]).reshape(S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)      # query head i reads KV head i // (H/KV)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("shd,thd->hst", q, k, precision=HIGHEST) * hd ** -0.5
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hst,thd->shd", p, v, precision=HIGHEST)
    x = x + _mm(o.reshape(S, H * hd), w["wo"])
    h = _rms(x, w["mlp_norm"], dims.eps)
    return x + _mm(jax.nn.silu(_mm(h, w["w_gate"])) * _mm(h, w["w_up"]),
                   w["w_down"])


@functools.partial(jax.jit, static_argnums=(0, 3))
def _logits(dims: Dims, key, tokens, fp8: bool):
    k_outer, k_layers = _keys(key)
    outer = _f32(outer_weights(k_outer, dims), fp8)
    x = outer["embed"][tokens]

    def body(x, i):
        w = _f32(layer_weights(jax.random.fold_in(k_layers, i), dims), fp8)
        return _layer(dims, x, w), None

    x, _ = jax.lax.scan(body, x, jnp.arange(dims.layers))
    return _mm(_rms(x, outer["final_norm"], dims.eps), outer["lm_head"])


def logits(dims: Dims, seed: int, tokens, *, fp8: bool = False) -> jax.Array:
    """(S,) token ids -> (S, vocab) float32 next-token logits at every
    position, with the weights of :func:`init_weights` for ``seed``
    drawn again layer by layer."""
    return _logits(dims, root_key(seed), jnp.asarray(tokens, jnp.int32),
                   bool(fp8))
