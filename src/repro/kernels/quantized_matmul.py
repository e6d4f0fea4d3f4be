"""Int8 zero-stall matmul — the revolving-buffer schedule at 1 byte/elem.

Same machinery as :mod:`repro.kernels.zero_stall_matmul` (grid loop
nest = ZONL, N-slot VMEM revolving buffer = generalized Dobu), with
three quantization-specific changes:

* operands are **int8 codes** — every A/B tile DMA moves half the
  bytes of bf16, so the ``max(compute, dma)`` steady state of the
  pipeline model shifts toward compute-bound (the precision-scaled
  roofline of PAPERS.md);
* accumulation is **exact int32** (int8 products are <= 127², so int32
  never rounds and overflows only past K ~ 1.3e5 — far beyond any
  assigned shape), matching the MXU's native int8 datapath;
* the epilogue **fuses dequantization**: at the last k-step the int32
  accumulator is scaled by ``row_scale * col_scale`` (per-row
  activation scales x per-channel weight scales, streamed in as small
  BlockSpec operands) and cast to the output dtype — no second pass
  over C.

Because the schedule is unchanged, everything built on it transfers:
:class:`repro.core.pipeline.RevolvingSchedule` invariants,
:class:`repro.core.cyclemodel.TpuPipelineModel` estimates (with
``dtype_bytes=1`` and the int8 peak), and the :mod:`repro.tune` search
axes — the tuner just sees a bigger legal tile space under the halved
VMEM footprint.
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.meta import (compiler_params, kernel_name,
                                 register_family)
from repro.kernels.zero_stall_matmul import resolve_slots

_META = register_family("quantized_zero_stall_matmul", grid_rank=3,
                        managed_dma=True, sequential_axes="all")
_GROUPED_META = register_family("quantized_grouped_zero_stall_matmul",
                                grid_rank=4, managed_dma=True,
                                sequential_axes="all")

__all__ = ["quantized_zero_stall_matmul", "quantized_grouped_zero_stall_matmul"]


def _kernel(a_hbm, b_hbm, sa_ref, sb_ref, c_ref, a_vmem, b_vmem, acc,
            sem_a, sem_b, *, bm: int, bn: int, bk: int, slots: int,
            out_dtype, grid_shape: tuple[int, int, int], order: str):
    """Body; identical schedule to zero_stall_matmul._kernel, int32 acc."""
    p0, p1, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    g0, g1, gk = grid_shape
    total = g0 * g1 * gk
    i, j = (p0, p1) if order == "ijk" else (p1, p0)
    t = (p0 * g1 + p1) * gk + k

    def ijk_of(tt):
        q0 = tt // (g1 * gk)
        q1 = (tt // gk) % g1
        kk = tt % gk
        return ((q0, q1, kk) if order == "ijk" else (q1, q0, kk))

    def tile_copy(ii, jj, kk, slot):
        cp_a = pltpu.make_async_copy(
            a_hbm.at[pl.ds(ii * bm, bm), pl.ds(kk * bk, bk)],
            a_vmem.at[slot], sem_a.at[slot])
        cp_b = pltpu.make_async_copy(
            b_hbm.at[pl.ds(kk * bk, bk), pl.ds(jj * bn, bn)],
            b_vmem.at[slot], sem_b.at[slot])
        return cp_a, cp_b

    slot = jax.lax.rem(t, slots)

    @pl.when(t == 0)
    def _():
        for s in range(min(slots, total)):
            i_s, j_s, k_s = ijk_of(jnp.int32(s))
            for cp in tile_copy(i_s, j_s, k_s, s):
                cp.start()

    if slots > 1:
        look = slots - 1
        @pl.when(jnp.logical_and(t > 0, t + look < total))
        def _():
            t_n = t + look
            i_n, j_n, k_n = ijk_of(t_n)
            for cp in tile_copy(i_n, j_n, k_n, jax.lax.rem(t_n, slots)):
                cp.start()

    for cp in tile_copy(i, j, k, slot):
        cp.wait()

    prod = jnp.dot(a_vmem[slot], b_vmem[slot],
                   preferred_element_type=jnp.int32)

    @pl.when(k == 0)
    def _():
        acc[...] = prod

    @pl.when(k != 0)
    def _():
        acc[...] = acc[...] + prod

    @pl.when(k == gk - 1)
    def _():
        # fused dequant epilogue: (bm,1) row scales x (1,bn) col scales
        c = acc[...].astype(jnp.float32) * sa_ref[...] * sb_ref[...]
        c_ref[...] = c.astype(out_dtype)

    if slots == 1:
        @pl.when(t + 1 < total)
        def _():
            i_n, j_n, k_n = ijk_of(t + 1)
            for cp in tile_copy(i_n, j_n, k_n, slot):
                cp.start()


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "variant", "slots", "grid_order",
                     "interpret", "out_dtype"))
def quantized_zero_stall_matmul(
    a: jax.Array,          # (M, K) int8 codes
    b: jax.Array,          # (K, N) int8 codes
    a_scale: jax.Array,    # (M, 1) fp32 per-row scales
    b_scale: jax.Array,    # (1, N) fp32 per-channel scales
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    variant: Literal["dobu", "single"] = "dobu",
    slots: int | None = None,
    grid_order: Literal["ijk", "jik"] = "ijk",
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    """C = (a·b) * a_scale * b_scale with the zero-stall schedule.

    Operands are int8 codes; ``ops.quantized_matmul`` produces them
    (dynamic per-row activation quantization + QTensor weights) and
    pads arbitrary shapes to tile multiples — zero codes contribute
    exact integer zeros, so padding never changes the math.
    """
    (M, K), (K2, N) = a.shape, b.shape
    if K != K2:
        raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
    if a.dtype != jnp.int8 or b.dtype != jnp.int8:
        raise ValueError(f"operands must be int8, got {a.dtype}/{b.dtype}")
    if a_scale.shape != (M, 1) or b_scale.shape != (1, N):
        raise ValueError(f"scale shapes {a_scale.shape}/{b_scale.shape} "
                         f"must be {(M, 1)}/{(1, N)}")
    if M % bm or N % bn or K % bk:
        raise ValueError(f"shapes {(M, K, N)} not multiples of tiles "
                         f"{(bm, bk, bn)}")
    if grid_order not in ("ijk", "jik"):
        raise ValueError(f"grid_order must be 'ijk' or 'jik', got {grid_order!r}")
    slots = resolve_slots(variant, slots)
    gm, gn, gk = M // bm, N // bn, K // bk
    grid = (gm, gn, gk) if grid_order == "ijk" else (gn, gm, gk)
    if grid_order == "ijk":
        def sa_map(i, j, k):
            return (i, 0)

        def sb_map(i, j, k):
            return (0, j)

        def out_map(i, j, k):
            return (i, j)
    else:
        def sa_map(j, i, k):
            return (i, 0)

        def sb_map(j, i, k):
            return (0, j)

        def out_map(j, i, k):
            return (i, j)

    kernel = functools.partial(
        _kernel, bm=bm, bn=bn, bk=bk, slots=slots, out_dtype=out_dtype,
        grid_shape=grid, order=grid_order)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),       # A codes stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),       # B codes stay in HBM
            pl.BlockSpec((bm, 1), sa_map),           # row scales (epilogue)
            pl.BlockSpec((1, bn), sb_map),           # col scales (epilogue)
        ],
        out_specs=pl.BlockSpec((bm, bn), out_map),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((slots, bm, bk), jnp.int8),   # revolving A slots
            pltpu.VMEM((slots, bk, bn), jnp.int8),   # revolving B slots
            pltpu.VMEM((bm, bn), jnp.int32),         # exact accumulator
            pltpu.SemaphoreType.DMA((slots,)),
            pltpu.SemaphoreType.DMA((slots,)),
        ],
        compiler_params=compiler_params("arbitrary", "arbitrary",
                                        "arbitrary"),
        interpret=interpret,
        name=kernel_name("quantized_zero_stall_matmul", slots=slots,
                         grid_order=grid_order),
    )(a, b, a_scale.astype(jnp.float32), b_scale.astype(jnp.float32))


def _grouped_kernel(a_hbm, b_hbm, sa_ref, sb_ref, c_ref, a_vmem, b_vmem,
                    acc, sem_a, sem_b, *, bm, bn, bk, slots, out_dtype,
                    grid_shape: tuple[int, int, int, int]):
    g, i, j, k = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                  pl.program_id(3))
    gg, gm, gn, gk = grid_shape
    total = gg * gm * gn * gk
    t = ((g * gm + i) * gn + j) * gk + k

    def gijk_of(tt):
        return (tt // (gm * gn * gk), (tt // (gn * gk)) % gm,
                (tt // gk) % gn, tt % gk)

    def tile_copy(ggi, ii, jj, kk, slot):
        cp_a = pltpu.make_async_copy(
            a_hbm.at[ggi, pl.ds(ii * bm, bm), pl.ds(kk * bk, bk)],
            a_vmem.at[slot], sem_a.at[slot])
        cp_b = pltpu.make_async_copy(
            b_hbm.at[ggi, pl.ds(kk * bk, bk), pl.ds(jj * bn, bn)],
            b_vmem.at[slot], sem_b.at[slot])
        return cp_a, cp_b

    slot = jax.lax.rem(t, slots)

    @pl.when(t == 0)
    def _():
        for s in range(min(slots, total)):
            g_s, i_s, j_s, k_s = gijk_of(jnp.int32(s))
            for cp in tile_copy(g_s, i_s, j_s, k_s, s):
                cp.start()

    if slots > 1:
        look = slots - 1
        @pl.when(jnp.logical_and(t > 0, t + look < total))
        def _():
            t_n = t + look
            g_n, i_n, j_n, k_n = gijk_of(t_n)
            for cp in tile_copy(g_n, i_n, j_n, k_n, jax.lax.rem(t_n, slots)):
                cp.start()

    for cp in tile_copy(g, i, j, k, slot):
        cp.wait()

    prod = jnp.dot(a_vmem[slot], b_vmem[slot],
                   preferred_element_type=jnp.int32)

    @pl.when(k == 0)
    def _():
        acc[...] = prod

    @pl.when(k != 0)
    def _():
        acc[...] = acc[...] + prod

    @pl.when(k == gk - 1)
    def _():
        c = acc[...].astype(jnp.float32) * sa_ref[0] * sb_ref[0]
        c_ref[0] = c.astype(out_dtype)

    if slots == 1:
        @pl.when(t + 1 < total)
        def _():
            g_n, i_n, j_n, k_n = gijk_of(t + 1)
            for cp in tile_copy(g_n, i_n, j_n, k_n, slot):
                cp.start()


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "variant", "slots", "interpret",
                     "out_dtype"))
def quantized_grouped_zero_stall_matmul(
    a: jax.Array,          # (G, M, K) int8 codes
    b: jax.Array,          # (G, K, N) int8 codes
    a_scale: jax.Array,    # (G, M, 1) fp32 per-row scales
    b_scale: jax.Array,    # (G, 1, N) fp32 per-(expert, channel) scales
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    variant: Literal["dobu", "single"] = "dobu",
    slots: int | None = None,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Per-expert int8 matmul; the revolving buffer streams across
    expert boundaries exactly as in ``grouped_zero_stall_matmul``."""
    (G, M, K), (G2, K2, N) = a.shape, b.shape
    if G != G2 or K != K2:
        raise ValueError(f"group/contraction mismatch: {a.shape} @ {b.shape}")
    if a.dtype != jnp.int8 or b.dtype != jnp.int8:
        raise ValueError(f"operands must be int8, got {a.dtype}/{b.dtype}")
    if a_scale.shape != (G, M, 1) or b_scale.shape != (G, 1, N):
        raise ValueError(f"scale shapes {a_scale.shape}/{b_scale.shape} "
                         f"must be {(G, M, 1)}/{(G, 1, N)}")
    if M % bm or N % bn or K % bk:
        raise ValueError(f"{(M, K, N)} not multiples of {(bm, bk, bn)}")
    slots = resolve_slots(variant, slots)
    gm, gn, gk = M // bm, N // bn, K // bk

    kernel = functools.partial(
        _grouped_kernel, bm=bm, bn=bn, bk=bk, slots=slots,
        out_dtype=out_dtype, grid_shape=(G, gm, gn, gk))

    return pl.pallas_call(
        kernel,
        grid=(G, gm, gn, gk),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, bm, 1), lambda g, i, j, k: (g, i, 0)),
            pl.BlockSpec((1, 1, bn), lambda g, i, j, k: (g, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, j, k: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((G, M, N), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((slots, bm, bk), jnp.int8),
            pltpu.VMEM((slots, bk, bn), jnp.int8),
            pltpu.VMEM((bm, bn), jnp.int32),
            pltpu.SemaphoreType.DMA((slots,)),
            pltpu.SemaphoreType.DMA((slots,)),
        ],
        compiler_params=compiler_params(*("arbitrary",) * 4),
        interpret=interpret,
        name=kernel_name("quantized_grouped_zero_stall_matmul",
                         slots=slots),
    )(a, b, a_scale.astype(jnp.float32), b_scale.astype(jnp.float32))
