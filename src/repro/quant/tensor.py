"""QTensor: per-channel symmetric quantized weights as a pytree.

A :class:`QTensor` packs a quantized weight — narrow integer (or
simulated-fp8) codes plus the fp32 per-channel scales that map them
back to real values — and registers as a JAX pytree so it can ride
anywhere a plain weight array could: through ``jax.jit`` / ``vmap``
closures, ``lax.scan`` over stacked layers (the scan slices ``data``
and ``scale`` in lockstep), checkpoint save/restore (the leaves are
ordinary arrays), and the serving engine's params argument.

Quantization is **symmetric per output channel**: for a weight laid
out ``(..., d_in, d_out)`` the scale is the absmax over the
contraction axis (``axis=-2``), shape ``(..., 1, d_out)``, so each
output channel of ``x @ w`` sees its own dynamic range.  This is the
layout every matmul weight in the repo uses — 2D linear weights,
``(n_layers, d_in, d_out)`` scan-stacked weights, and
``(n_experts, d_in, d_out)`` MoE expert banks — so one rule covers
all five model families.

Formats:

* ``"int8"`` — codes in ``[-127, 127]``; the real quantized compute
  path (:func:`repro.kernels.ops.quantized_matmul` runs an int8
  zero-stall Pallas kernel with exact int32 accumulation and a fused
  dequantizing epilogue).
* ``"fp8"``  — *simulated* fp8 (e4m3): the storage rounding is real
  (values snap to the e4m3 grid under a per-channel scale), the
  compute dequantizes to the activation dtype and runs the standard
  bf16/fp32 zero-stall kernel.  This isolates fp8's numerics from
  int8's while this JAX version lacks an fp8 MXU path.

>>> import jax.numpy as jnp
>>> w = jnp.array([[1.0, -2.0], [3.0, 4.0]])
>>> qt = quantize(w)
>>> qt.data.dtype.name, qt.scale.shape
('int8', (1, 2))
>>> bool(jnp.abs(qt.dequantize() - w).max() <= 4.0 / 127)
True
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

__all__ = ["QTensor", "quantize", "quantize_rows", "quantize_tree",
           "INT8_MAX", "FP8_MAX"]

INT8_MAX = 127.0          # symmetric: -127..127 (never -128, keeps |q| even)
FP8_MAX = 448.0           # float8_e4m3 largest finite magnitude

_FP8_DTYPE = jnp.float8_e4m3fn


@jax.tree_util.register_pytree_node_class
class QTensor:
    """Quantized weight: integer/fp8 ``data`` + fp32 per-channel ``scale``.

    ``data``: the codes, dtype int8 (fmt="int8") or float8_e4m3
    (fmt="fp8"); same shape as the original weight.
    ``scale``: fp32, shape ``data.shape`` with the contraction axis
    (``-2``) reduced to 1 — real value ≈ ``data * scale``.
    ``fmt`` and ``w8a8`` are static pytree metadata, so jit caches
    specialize per format without retracing on new weights.

    ``w8a8=False`` marks a weight whose *activations* must stay full
    precision (W8A16: quantized storage, dequantize-on-the-fly
    compute).  :func:`quantize_tree` sets it for the SSM block
    projections, where the SSD recurrence exponentially amplifies
    activation-quantization noise (measured: the hybrid family blows
    past 5% logit error under full W8A8 but stays under 4% with
    W8A16 SSM projections — the same split quantized-Mamba work
    converged on).
    """

    def __init__(self, data: jax.Array, scale: jax.Array,
                 fmt: str = "int8", w8a8: bool = True):
        self.data = data
        self.scale = scale
        self.fmt = fmt
        self.w8a8 = w8a8

    # -- pytree protocol ------------------------------------------------
    def tree_flatten(self):
        return (self.data, self.scale), (self.fmt, self.w8a8)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, scale = children
        fmt, w8a8 = aux
        return cls(data, scale, fmt, w8a8)

    # -- views ----------------------------------------------------------
    @property
    def shape(self) -> tuple[int, ...]:
        return self.data.shape

    @property
    def ndim(self) -> int:
        return self.data.ndim

    def dequantize(self, dtype: Any = None) -> jax.Array:
        """Real-valued weight, in ``dtype`` (default fp32)."""
        w = self.data.astype(jnp.float32) * self.scale.astype(jnp.float32)
        return w.astype(dtype) if dtype is not None else w

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"QTensor(fmt={self.fmt!r}, shape={self.data.shape}, "
                f"scale_shape={self.scale.shape})")


def _absmax_scale(w: jax.Array, axis: int, qmax: float) -> jax.Array:
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = amax / qmax
    # all-zero channels (padding, unused experts) quantize to zeros with
    # a unit scale instead of dividing by zero
    return jnp.where(scale == 0.0, 1.0, scale)


def quantize(w: jax.Array, *, fmt: str = "int8", axis: int = -2,
             w8a8: bool = True) -> QTensor:
    """Per-channel symmetric quantization of a weight.

    ``axis`` is the contraction (input) axis the scale reduces over;
    the default ``-2`` matches the repo's universal ``(..., d_in,
    d_out)`` weight layout.  Leading axes (scan-stacked layers, MoE
    experts, hybrid layer groups) are preserved, so the scales slice
    alongside the codes under ``lax.scan`` / ``vmap``.  ``w8a8=False``
    pins the weight to the W8A16 path (see :class:`QTensor`).
    """
    if fmt == "int8":
        scale = _absmax_scale(w, axis, INT8_MAX)
        q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale),
                     -INT8_MAX, INT8_MAX).astype(jnp.int8)
        return QTensor(q, scale, "int8", w8a8)
    if fmt == "fp8":
        scale = _absmax_scale(w, axis, FP8_MAX)
        q = (w.astype(jnp.float32) / scale).astype(_FP8_DTYPE)
        return QTensor(q, scale, "fp8", w8a8)
    raise ValueError(f"fmt must be 'int8' or 'fp8', got {fmt!r}")


def quantize_rows(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Dynamic per-row int8 quantization of an activation ``(..., M, K)``.

    Returns ``(codes int8, scale fp32 (..., M, 1))``.  Per-row (= per
    token) scales keep the quantized serving path lengths-aware for
    free: padding rows are exact zeros, quantize to zero codes, and
    contribute exact zeros to the integer contraction — the same
    invariant the fp kernels rely on.
    """
    scale = _absmax_scale(x, -1, INT8_MAX)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                 -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return q, scale


#: params-dict keys holding raw (non-dict) MoE expert weight banks
_EXPERT_KEYS = ("wi", "wg", "wo")

#: linear layers whose ACTIVATIONS stay full precision (W8A16): the
#: mamba projections feed the SSD recurrence, whose exp(cumsum)
#: decays amplify activation-quantization noise exponentially over
#: the sequence (measured on the hybrid family; see QTensor.w8a8).
_W8A16_KEYS = ("in_proj", "out_proj")


def quantize_tree(params: Any, *, fmt: str = "int8") -> Any:
    """Quantize every matmul weight in a model params pytree.

    The rule mirrors how the repo lays out params: a ``{"w": ...}``
    dict is a linear layer (``layers.init_linear``) — its ``w`` leaf is
    quantized; raw ``wi``/``wg``/``wo`` arrays of rank >= 3 are MoE
    expert banks (``moe.init_moe_mlp``) — quantized per expert.
    Everything else (embeddings, norms, convs, SSM decay/dt params,
    routers, biases) keeps full precision: they are either not matmul
    operands or too precision-sensitive for their negligible FLOP
    share.  SSM projections (``in_proj``/``out_proj``) are quantized
    W8A16 (``w8a8=False``).  Idempotent: already-quantized leaves pass
    through.
    """
    def walk(node, parent_key=None):
        if not isinstance(node, dict):
            return node
        out = {}
        for key, val in node.items():
            if isinstance(val, dict):
                out[key] = walk(val, key)
            elif isinstance(val, QTensor):
                out[key] = val
            elif key == "w" and getattr(val, "ndim", 0) >= 2:
                out[key] = quantize(val, fmt=fmt,
                                    w8a8=parent_key not in _W8A16_KEYS)
            elif key in _EXPERT_KEYS and getattr(val, "ndim", 0) >= 3:
                out[key] = quantize(val, fmt=fmt)
            else:
                out[key] = val
        return out

    return walk(params)
