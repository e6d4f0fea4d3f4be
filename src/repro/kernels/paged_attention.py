"""Paged decode attention: page-table gather driven by scalar prefetch.

The serving engine's paged KV cache stores every slot's keys/values in
a shared page pool ``(num_pages, page_size, KV, D)`` addressed through
a per-slot int32 page table.  This kernel keeps the decode step on
Pallas by turning the table walk into a *BlockSpec gather*: the page
table is scalar-prefetched into SMEM and the K/V index maps read it to
pick the physical page for each grid step — the pipeline then streams
exactly the pages the sequence owns, double-buffered by construction,
never materializing a gathered (B, max_len, ...) copy in HBM.  That is
the zero-conflict property at serving granularity: a page is a bank,
the table is the conflict-free mapping, and the revolving-buffer
schedule stays the grid itself.

Layout: decode has one query token per sequence.  Grouped-query
attention rides the query *rows*: q ``(B, H, D)`` is reshaped to
``(B*KV, rep, D)`` (``rep = H // KV`` query heads that share one KV
head), so the grid is ``(B*KV, T)`` with the T page steps innermost.
Online softmax state (running max / denom / accumulator) lives in VMEM
scratch exactly as in :mod:`repro.kernels.flash_attention`.

Masking: the query is the sequence's last position, so no causal test
is needed — only ``cols < kv_len``.  Pages past the valid length
(including the reserved trash page 0 that retired slots' tables point
at) mask to exact zero weight.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.meta import (compiler_params, kernel_name,
                                 register_family)

_META = register_family("paged_attention", grid_rank=2,
                        managed_dma=False, sequential_axes="last")

__all__ = ["paged_attention"]

NEG_INF = -1e30


def _kernel(pt_ref, kl_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *,
            scale: float, ps: int, kv_heads: int):
    g = pl.program_id(0)               # b * KV + kv_head
    j = pl.program_id(1)               # logical page index
    nT = pl.num_programs(1)
    kv_len = kl_ref[g // kv_heads]

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                       # (rep, D)
    k = k_ref[0]                       # (ps, D) — the gathered page
    v = v_ref[0]                       # (ps, D)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (rep, ps)

    cols = j * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(cols < kv_len, s, NEG_INF)

    m_prev = m_scr[...]                # (rep, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # Same fully-masked-row guard as the flash kernel: while a row has
    # seen no valid kv position, keep l == 0 so it resolves to zeros.
    p = jnp.where(m_new > 0.5 * NEG_INF, jnp.exp(s - m_new), 0.0)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)

    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(j == nT - 1)
    def _():
        den = l_scr[...]
        safe = jnp.where(den == 0.0, 1.0, den)
        o_ref[0] = (acc_scr[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention(
    q: jax.Array,            # (B, H, D) one decode query per sequence
    k_pool: jax.Array,       # (P, ps, KV, D) shared page pool
    v_pool: jax.Array,       # (P, ps, KV, D)
    page_table: jax.Array,   # (B, T) int32 logical -> physical page
    *,
    kv_lens: jax.Array,      # (B,) valid kv positions (cache pos + 1)
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    B, H, D = q.shape
    P, ps, KV = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    T = page_table.shape[1]
    if H % KV:
        raise ValueError(f"q heads {H} not a multiple of kv heads {KV}")
    rep = H // KV
    scale = scale if scale is not None else D ** -0.5

    # GQA: the rep query heads sharing one kv head become the query rows
    # of one grid step, so each gathered page is read once per kv head.
    qf = q.reshape(B * KV, rep, D)
    pt_flat = page_table.reshape(-1).astype(jnp.int32)     # (B*T,)
    # Each grid step reads one kv head of one page.  On the (P, ps, KV,
    # D) pool that block would be (1, ps, 1, D), whose second-minor
    # dimension (1 of KV) Mosaic refuses for KV > 1; folding the heads
    # into the lane axis (a free reshape) makes it a (ps, D) tile: a
    # full-extent sublane dimension and a 128-lane-aligned column
    # window at head g % KV.
    kf = k_pool.reshape(P, ps, KV * D)
    vf = v_pool.reshape(P, ps, KV * D)

    kernel = functools.partial(_kernel, scale=scale, ps=ps, kv_heads=KV)
    # K/V index maps do the page-table walk: grid step (g, j) pulls
    # physical page pt[b*T + j] for kv head g % KV.  Block index == page
    # id because the page axis block size is 1.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,      # page table, kv_lens -> SMEM
        grid=(B * KV, T),
        in_specs=[
            pl.BlockSpec((1, rep, D), lambda g, j, *_: (g, 0, 0)),
            pl.BlockSpec(
                (1, ps, D),
                lambda g, j, pt, kl: (pt[(g // KV) * T + j], 0, g % KV)),
            pl.BlockSpec(
                (1, ps, D),
                lambda g, j, pt, kl: (pt[(g // KV) * T + j], 0, g % KV)),
        ],
        out_specs=pl.BlockSpec((1, rep, D), lambda g, j, *_: (g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),    # running max
            pltpu.VMEM((rep, 1), jnp.float32),    # running denom
            pltpu.VMEM((rep, D), jnp.float32),    # output accumulator
        ],
    )
    of = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * KV, rep, D), q.dtype),
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name=kernel_name("paged_attention"),
    )(pt_flat, kv_lens.astype(jnp.int32), qf, kf, vf)
    return of.reshape(B, H, D)
