"""Blocked flash attention with dobu-style K/V tile streaming.

Attention is the second matmul hot-spot of the assigned architectures
(32k prefill).  The kernel streams K/V tiles through VMEM with online
softmax.  Here the revolving-buffer schedule is delegated to the Pallas
grid pipeline (BlockSpec-driven, double-buffered by construction) — the
paper's insight "producer/consumer must not contend" is expressed by
tiling the kv loop as the innermost grid dimension, so tile t+1's fetch
overlaps tile t's MXU work, and the zero-overhead loop nest is again
the grid itself.

Layout: q (B, H, S, D) -> grid (B*H, S/bq, S_kv/bkv), kv innermost.
Running max/denominator/accumulator live in VMEM scratch and are
carried across kv steps (the "revisiting output" pattern).

Variable-length batches (the serving workload): ``q_lens`` / ``kv_lens``
are per-sequence valid lengths, scalar-prefetched into SMEM so every
grid step can mask its score tile.  Rows/cols at ``>= len`` are invalid;
fully-masked query rows produce exact zeros.  Positions are absolute
row/col indices (query row i is sequence position ``q_offsets[b] + i``,
with offsets defaulting to zero), so zero-padding q/k/v up to tile
multiples never changes the math — that is what lets
:func:`repro.kernels.ops.attention` keep ragged continuous batches on
this kernel instead of falling back to the jnp reference.  Nonzero
``q_offsets`` serve chunked prefill: a chunk of query rows attends to
the slot's full kv stripe with its causal frontier shifted to the
chunk's absolute start.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.meta import (compiler_params, kernel_name,
                                 register_family)

# pipeline-managed double buffering (BlockSpec windows, no manual DMA);
# only the kv streaming axis (last) must stay sequential — m/l/acc are
# re-initialized at every j == 0
_META = register_family("flash_attention", grid_rank=3,
                        managed_dma=False, sequential_axes="last")

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _kernel(ql_ref, kl_ref, qo_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, bq: int, bkv: int, n_heads: int):
    b = pl.program_id(0)
    iq, ikv = pl.program_id(1), pl.program_id(2)
    nkv = pl.num_programs(2)
    q_len = ql_ref[b // n_heads]
    kv_len = kl_ref[b // n_heads]
    q_off = qo_ref[b // n_heads]

    @pl.when(ikv == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                       # (bq, D)
    k = k_ref[0]                       # (bkv, D)
    v = v_ref[0]                       # (bkv, D)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (bq, bkv)

    # query row i sits at absolute sequence position q_off + i (q_off is
    # nonzero only for chunked prefill, where the chunk's rows attend to
    # a kv stripe that starts before them)
    rows = q_off + iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    cols = ikv * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    valid = (rows < q_len) & (cols < kv_len)
    if causal:
        valid &= rows >= cols
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    # While a row has seen no valid kv position, m_new == NEG_INF and
    # exp(s - m_new) would be exp(0) == 1 for every masked entry,
    # polluting l/acc with garbage that no later rescale removes.
    # Predicate on m_new so fully-masked rows keep l == 0 (-> zeros out).
    p = jnp.where(m_new > 0.5 * NEG_INF, jnp.exp(s - m_new), 0.0)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)

    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ikv == nkv - 1)
    def _():
        # rows with no valid kv position (fully masked) produce l == 0
        den = l_scr[...]
        safe = jnp.where(den == 0.0, 1.0, den)
        o_ref[0] = (acc_scr[...] / safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bq", "bkv", "causal", "scale", "interpret"))
def flash_attention(
    q: jax.Array,   # (B, H, Sq, D)
    k: jax.Array,   # (B, H, Skv, D)
    v: jax.Array,   # (B, H, Skv, D)
    *,
    q_lens: jax.Array | None = None,    # (B,) valid query rows
    kv_lens: jax.Array | None = None,   # (B,) valid kv positions
    q_offsets: jax.Array | None = None, # (B,) absolute offset of query row 0
    bq: int = 128,
    bkv: int = 128,
    causal: bool = True,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    B, H, Sq, D = q.shape
    Skv = k.shape[2]
    if Sq % bq or Skv % bkv:
        raise ValueError(f"seq lens {(Sq, Skv)} not multiples of {(bq, bkv)}")
    scale = scale if scale is not None else D ** -0.5
    if q_offsets is None:
        q_offsets = jnp.zeros((B,), jnp.int32)
    if q_lens is None:
        # default: all Sq rows valid — in absolute positions when the
        # rows are offset
        q_lens = q_offsets.astype(jnp.int32) + Sq
    if kv_lens is None:
        kv_lens = jnp.full((B,), Skv, jnp.int32)
    bh = B * H
    qf = q.reshape(bh, Sq, D)
    kf = k.reshape(bh, Skv, D)
    vf = v.reshape(bh, Skv, D)

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               bq=bq, bkv=bkv, n_heads=H)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,      # q_lens, kv_lens, q_offsets -> SMEM
        grid=(bh, Sq // bq, Skv // bkv),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, bkv, D), lambda b, i, j, *_: (b, j, 0)),
            pl.BlockSpec((1, bkv, D), lambda b, i, j, *_: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j, *_: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running denom
            pltpu.VMEM((bq, D), jnp.float32),    # output accumulator
        ],
    )
    of = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, Sq, D), q.dtype),
        compiler_params=compiler_params("parallel", "arbitrary",
                                        "arbitrary"),
        interpret=interpret,
        name=kernel_name("flash_attention"),
    )(q_lens.astype(jnp.int32), kv_lens.astype(jnp.int32),
      q_offsets.astype(jnp.int32), qf, kf, vf)
    return of.reshape(B, H, Sq, D)
