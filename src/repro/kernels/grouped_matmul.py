"""Grouped zero-stall matmul — the paper's technique applied to MoE.

Per-expert FFN matmuls (x_g @ W_g for every expert g) are the dominant
compute of the assigned MoE architectures (granite-moe 32e, olmoe 64e).
The kernel extends :mod:`zero_stall_matmul`'s dobu pipeline with a
leading group dimension: the revolving N-slot VMEM buffer ("hyperbank"
parity at arbitrary depth) streams *across expert boundaries*, so the
MXU never waits for an expert switch — expert g+1's first tiles are
DMA'd while expert g's last tiles are multiplied.  This is exactly the
paper's zero-conflict double-buffering, applied where a specialized
accelerator could not reach (dynamic expert dispatch).

Buffer depth (``slots``) is a search axis of :mod:`repro.tune`; the
schedule is the same generalized revolving buffer as
``zero_stall_matmul`` (prologue fills every slot, steady state
prefetches step t+slots-1 into the slot drained at step t-1).
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

_META = register_family("grouped_zero_stall_matmul", grid_rank=4,
                        managed_dma=True, sequential_axes="all")

__all__ = ["grouped_zero_stall_matmul"]


def _kernel(a_hbm, b_hbm, c_ref, a_vmem, b_vmem, acc, sem_a, sem_b, *,
            bm, bn, bk, slots, out_dtype,
            grid_shape: tuple[int, int, int, int]):
    g, i, j, k = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                  pl.program_id(3))
    gg, gm, gn, gk = grid_shape       # static (wrapper-provided)
    total = gg * gm * gn * gk
    t = ((g * gm + i) * gn + j) * gk + k

    def gijk_of(tt):
        """(g, i, j, k) of linear step `tt` (k fastest)."""
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

    # prologue: first step fills every slot (steps 0..slots-1)
    @pl.when(t == 0)
    def _():
        for s in range(min(slots, total)):
            g_s, i_s, j_s, k_s = gijk_of(jnp.int32(s))
            for cp in tile_copy(g_s, i_s, j_s, k_s, s):
                cp.start()

    # revolving prefetch: fill the slot step t+slots-1 will consume
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
                   preferred_element_type=jnp.float32)

    @pl.when(k == 0)
    def _():
        acc[...] = prod

    @pl.when(k != 0)
    def _():
        acc[...] = acc[...] + prod

    @pl.when(k == gk - 1)
    def _():
        c_ref[0] = acc[...].astype(out_dtype)

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
def grouped_zero_stall_matmul(
    a: jax.Array,                 # (G, M, K)
    b: jax.Array,                 # (G, K, N)
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    variant: Literal["dobu", "single"] = "dobu",
    slots: int | None = None,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    (G, M, K), (G2, K2, N) = a.shape, b.shape
    if G != G2 or K != K2:
        raise ValueError(f"group/contraction mismatch: {a.shape} @ {b.shape}")
    if M % bm or N % bn or K % bk:
        raise ValueError(f"{(M, K, N)} not multiples of {(bm, bk, bn)}")
    out_dtype = out_dtype or a.dtype
    slots = resolve_slots(variant, slots)
    gm, gn, gk = M // bm, N // bn, K // bk

    kernel = functools.partial(
        _kernel, bm=bm, bn=bn, bk=bk, slots=slots, out_dtype=out_dtype,
        grid_shape=(G, gm, gn, gk))

    return pl.pallas_call(
        kernel,
        grid=(G, gm, gn, gk),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, j, k: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((G, M, N), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((slots, bm, bk), a.dtype),
            pltpu.VMEM((slots, bk, bn), b.dtype),
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.SemaphoreType.DMA((slots,)),
            pltpu.SemaphoreType.DMA((slots,)),
        ],
        compiler_params=compiler_params(*("arbitrary",) * 4),
        interpret=interpret,
        name=kernel_name("grouped_zero_stall_matmul", slots=slots),
    )(a, b)
