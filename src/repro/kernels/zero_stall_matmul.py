"""Zero-stall matmul — the paper's technique as a Pallas TPU kernel.

Mapping of the paper's two mechanisms (DESIGN.md §2):

* **Zero-overhead loop nest** → the whole (m, n, k) tile loop is the
  `pallas_call` grid.  The TPU scalar core sequences grid steps while
  the MXU computes, so tile-loop bookkeeping costs zero issue slots —
  exactly what the generalized FREP sequencer buys the Snitch cluster.
  (The pre-ZONL baseline — a host-driven tile loop paying dispatch
  per tile — lives in ``ops.host_tiled_matmul``.)

* **Zero-conflict (Dobu) memory subsystem** → operands stay in HBM
  (`memory_space=ANY`) and are explicitly DMA'd into an **N-slot VMEM
  revolving buffer** (`pltpu.make_async_copy` + DMA semaphores).  While
  the MXU consumes slot ``t % N``, the DMA engine fills the slot that
  step ``t + N - 1`` will consume — the slot residue IS the hyperbank
  parity, generalized to arbitrary depth: producer and consumer are
  structurally separated, so they never contend.  ``slots=2`` is the
  paper's exact 2-hyperbank scheme; ``slots>2`` keeps more DMAs in
  flight (tolerates HBM latency jitter at the price of VMEM).  The
  ``slots=1`` (``single``) variant — copy → wait → compute
  serialization — is the "conflicted" baseline (Base32fc analogue).

Buffer depth is a first-class search axis of :mod:`repro.tune`, which
picks ``(bm, bn, bk, slots, grid_order)`` per problem shape under the
VMEM budget.

The N-slot schedule; grid step ``t`` (linearized, k fastest):

    t == 0:            start DMA(step s → slot s) for s < slots
    t > 0, t+slots-1 < T:  start DMA(step t+slots-1 → slot (t+slots-1) % N)
    wait  DMA(slot t % N)
    k == 0:            acc  = A·B          (paper: peeled fmul iteration)
    else:              acc += A·B
    k == gk-1:         C_tile = acc        (paper: writeback-SSR fmadd)

Slot ``(t+slots-1) % N == (t-1) % N`` was consumed at step ``t-1``, so
the prefetch never lands in a live slot (the Dobu invariant, checked by
:class:`repro.core.pipeline.RevolvingSchedule`).

All grid dimensions are declared "arbitrary" (sequential) because the
cross-step prefetch carries state between steps — the same reason the
FREP ring buffer is a sequential structure.
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
# canonical (variant, slots) rules — shared with KernelConfig validation
from repro.plan.config import resolve_slots

__all__ = ["zero_stall_matmul", "DEFAULT_TILES", "resolve_slots"]

DEFAULT_TILES = (128, 128, 128)  # MXU-aligned (multiples of 128)

# manual-DMA revolving buffer: every grid axis carries DMA/accumulator
# state, so all three must stay sequential ("arbitrary")
_META = register_family("zero_stall_matmul", grid_rank=3,
                        managed_dma=True, sequential_axes="all")


def _kernel(a_hbm, b_hbm, row0_ref, c_ref, a_vmem, b_vmem, acc, sem_a,
            sem_b, *, bm: int, bn: int, bk: int, slots: int, out_dtype,
            grid_shape: tuple[int, int, int], order: str):
    """Kernel body; a_vmem/b_vmem have a leading `slots` dimension.
    B's contraction rows start at row ``row0_ref[0]`` of ``b_hbm``."""
    p0, p1, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    row0 = row0_ref[0]
    g0, g1, gk = grid_shape          # static (wrapper-provided)
    total = g0 * g1 * gk
    i, j = (p0, p1) if order == "ijk" else (p1, p0)
    t = (p0 * g1 + p1) * gk + k

    def ijk_of(tt):
        """(i, j, k) of linear step `tt` under this grid order."""
        q0 = tt // (g1 * gk)
        q1 = (tt // gk) % g1
        kk = tt % gk
        return ((q0, q1, kk) if order == "ijk" else (q1, q0, kk))

    def tile_copy(ii, jj, kk, slot):
        """DMA descriptors for step (ii,jj,kk) into `slot`."""
        cp_a = pltpu.make_async_copy(
            a_hbm.at[pl.ds(ii * bm, bm), pl.ds(kk * bk, bk)],
            a_vmem.at[slot], sem_a.at[slot])
        cp_b = pltpu.make_async_copy(
            b_hbm.at[pl.ds(pl.multiple_of(row0 + kk * bk, bk), bk),
                     pl.ds(jj * bn, bn)],
            b_vmem.at[slot], sem_b.at[slot])
        return cp_a, cp_b

    slot = jax.lax.rem(t, slots)

    # --- prologue: first step fills every slot (steps 0..slots-1) -----
    @pl.when(t == 0)
    def _():
        for s in range(min(slots, total)):
            i_s, j_s, k_s = ijk_of(jnp.int32(s))
            cp_a, cp_b = tile_copy(i_s, j_s, k_s, s)
            cp_a.start()
            cp_b.start()

    # --- revolving prefetch: fill the slot step t+slots-1 will use ----
    # That slot, (t-1) % slots, was drained at step t-1 — the Dobu
    # hyperbank invariant at depth N (RevolvingSchedule.conflict_free).
    if slots > 1:
        look = slots - 1
        @pl.when(jnp.logical_and(t > 0, t + look < total))
        def _():
            t_n = t + look
            i_n, j_n, k_n = ijk_of(t_n)
            cp_a, cp_b = tile_copy(i_n, j_n, k_n, jax.lax.rem(t_n, slots))
            cp_a.start()
            cp_b.start()

    # --- consume: wait for this step's slot ---------------------------
    cp_a, cp_b = tile_copy(i, j, k, slot)
    cp_a.wait()
    cp_b.wait()

    # --- single-buffered baseline: issue next copy only *after* use ---
    # (done post-compute below, so DMA and MXU serialize — the
    # "bank-conflict" analogue.)

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
        c_ref[...] = acc[...].astype(out_dtype)

    if slots == 1:
        @pl.when(t + 1 < total)
        def _():
            i_n, j_n, k_n = ijk_of(t + 1)
            cp_a, cp_b = tile_copy(i_n, j_n, k_n, slot)
            cp_a.start()
            cp_b.start()


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "variant", "slots", "grid_order",
                     "interpret", "out_dtype"))
def zero_stall_matmul(
    a: jax.Array,
    b: jax.Array,
    row0: jax.Array | int = 0,
    *,
    bm: int = DEFAULT_TILES[0],
    bn: int = DEFAULT_TILES[1],
    bk: int = DEFAULT_TILES[2],
    variant: Literal["dobu", "single"] = "dobu",
    slots: int | None = None,
    grid_order: Literal["ijk", "jik"] = "ijk",
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """C = A @ B with explicit zero-stall tiling.

    A: (M, K), B: (R, N) with R a multiple of K; the contraction reads
    B's rows ``row0 .. row0 + K``.  An ordinary product passes B as
    (K, N) and ``row0 = 0``; a stacked (L, K, N) weight reshaped to
    (L*K, N) with ``row0 = layer * K`` is read in place, one layer's
    tiles DMA'd straight out of the stack.  ``row0`` may be traced (it
    rides into the kernel as an int32 SMEM operand after A and B) and
    must be a multiple of ``bk``.  M, N, K must be multiples of the
    tile sizes (``ops.matmul`` pads arbitrary shapes before calling
    this).

    ``slots`` sets the revolving-buffer depth (None → 2 for "dobu",
    1 for "single"); ``grid_order`` picks which output dimension the
    outermost grid loop walks ("ijk" = rows outer, "jik" = cols outer —
    k stays fastest in both, as the accumulator requires).
    """
    (M, K), (R, N) = a.shape, b.shape
    if R < K or R % K:
        raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
    if M % bm or N % bn or K % bk:
        raise ValueError(f"shapes {(M, K, N)} not multiples of tiles {(bm, bk, bn)}")
    if isinstance(row0, int) and (row0 % bk or not 0 <= row0 <= R - K):
        raise ValueError(f"row offset {row0} is no multiple of bk={bk} "
                         f"inside B's {R} rows")
    if grid_order not in ("ijk", "jik"):
        raise ValueError(f"grid_order must be 'ijk' or 'jik', got {grid_order!r}")
    out_dtype = out_dtype or a.dtype
    slots = resolve_slots(variant, slots)
    gm, gn, gk = M // bm, N // bn, K // bk
    grid = (gm, gn, gk) if grid_order == "ijk" else (gn, gm, gk)
    out_map = ((lambda i, j, k: (i, j)) if grid_order == "ijk"
               else (lambda j, i, k: (i, j)))

    kernel = functools.partial(
        _kernel, bm=bm, bn=bn, bk=bk, slots=slots, out_dtype=out_dtype,
        grid_shape=grid, order=grid_order)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),   # A stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # B stays in HBM
            pl.BlockSpec(memory_space=pltpu.SMEM),   # B's first row
        ],
        out_specs=pl.BlockSpec((bm, bn), out_map),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((slots, bm, bk), a.dtype),   # "hyperbank" slots for A
            pltpu.VMEM((slots, bk, bn), b.dtype),   # "hyperbank" slots for B
            pltpu.VMEM((bm, bn), jnp.float32),      # accumulator
            pltpu.SemaphoreType.DMA((slots,)),
            pltpu.SemaphoreType.DMA((slots,)),
        ],
        compiler_params=compiler_params("arbitrary", "arbitrary",
                                        "arbitrary"),
        interpret=interpret,
        name=kernel_name("zero_stall_matmul", slots=slots,
                         grid_order=grid_order),
    )(a, b, jnp.asarray(row0, jnp.int32).reshape(1))
