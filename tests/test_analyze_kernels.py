"""repro.analyze.kernel_lint: the kernel-IR verifier.

Same contract as the other analyzer layers (test_analyze.py): the
repo's own kernels must sweep clean, and purpose-built mutants must be
rejected with *stable* rule ids:

  * a k-outermost grid walk that revisits an evicted output block
    -> ZS-K004 (broken HBM streaming);
  * a single-slot kernel issuing next-step prefetch *before* compute
    (overlap claimed with one buffer) -> ZS-K002 (in-flight WAR);
  * ``input_output_aliases`` writing a window a later grid step still
    reads -> ZS-K005;
  * a row offset that sends the matmul's B tiles past its layer stack
    -> ZS-K006.

The clean sweep here runs a trimmed space (one tile option) so tier-1
stays fast; CI's ``scripts/analyze.py --kernels`` gate runs the full
INTERPRET_SPACE sweep.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analyze import RULES, lint_kernels
from repro.analyze.kernel_lint import (KERNEL_FAMILIES, lint_kernel_ir,
                                       trace_kernel_irs)
from repro.kernels import ops
from repro.plan import KernelConfig
from repro.tune.space import KernelSpace

TRIM_SPACE = KernelSpace(tile_options=(8,), slot_options=(1, 2),
                         align=8, vmem_fraction=0.5,
                         int8_extra_tiles=())


# ----------------------------------------------------------------------
# IR extraction
# ----------------------------------------------------------------------
def test_trace_kernel_irs_extracts_grid_blocks_and_contract():
    a = jnp.ones((32, 32), jnp.float32)
    cfg = KernelConfig(backend="interpret", bm=8, bn=8, bk=8,
                       variant="dobu", slots=2)
    irs = trace_kernel_irs(ops.matmul, a, a, config=cfg)
    assert len(irs) == 1
    ir = irs[0]
    assert ir.name.startswith("zero_stall_matmul")
    assert ir.grid == (4, 4, 4)
    assert ir.total_steps == 64
    assert ir.contract is not None and ir.contract.managed_dma
    # manual-DMA operands stay unblocked; the output is windowed
    kinds = {(b.kind, b.blocked) for b in ir.blocks}
    assert ("out", True) in kinds


def test_kernel_rules_registered():
    for rule in ("ZS-K001", "ZS-K002", "ZS-K003", "ZS-K004", "ZS-K005",
                 "ZS-K006"):
        severity, layer, _ = RULES[rule]
        assert severity == "error"
        assert layer == "kernel-ir"


# ----------------------------------------------------------------------
# the repo's kernels sweep clean
# ----------------------------------------------------------------------
def test_all_families_clean_on_trimmed_space():
    report = lint_kernels(space=TRIM_SPACE)
    assert report.meta["zs_k_errors"] == 0
    assert not report.errors, report.format()
    assert set(report.meta["families"]) == set(KERNEL_FAMILIES)
    assert report.meta["kernels_verified"] >= len(KERNEL_FAMILIES)


def test_lint_kernels_rejects_unknown_family():
    with pytest.raises(ValueError, match="unknown kernel families"):
        lint_kernels(["warp_speed"])


# ----------------------------------------------------------------------
# mutation A: contraction axis outermost -> output block revisited
# ----------------------------------------------------------------------
def _k_outer_kernel(a_ref, o_ref):
    o_ref[...] = a_ref[...] * 1.0


def _k_outer(a):
    gi, gj, gk = 2, 2, 2
    return pl.pallas_call(
        _k_outer_kernel,
        grid=(gk, gi, gj),
        in_specs=[pl.BlockSpec((8, 8), lambda k, i, j: (i, j))],
        out_specs=pl.BlockSpec((8, 8), lambda k, i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((16, 16), jnp.float32),
        interpret=True,
        name="mutant_out_revisit",
    )(a)


def test_mutated_k_outer_grid_flags_zs_k004():
    a = jnp.ones((16, 16), jnp.float32)
    (ir,) = trace_kernel_irs(_k_outer, a)
    report = lint_kernel_ir(ir)
    assert "ZS-K004" in report.rules(), report.format()
    assert any("revisits output block" in d.message
               for d in report.errors)


# ----------------------------------------------------------------------
# mutation B: slots=1 but next-step prefetch issued pre-compute ->
# in-flight DMA into the slot the step is reading (WAR)
# ----------------------------------------------------------------------
_BM = _BN = _BK = 8


def _s1_overlap_kernel(a_hbm, b_hbm, c_ref, a_vmem, b_vmem, acc,
                       sem_a, sem_b):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    g1, gk = pl.num_programs(1), pl.num_programs(2)
    total = pl.num_programs(0) * g1 * gk
    t = (i * g1 + j) * gk + k

    def ijk_of(tt):
        return tt // (g1 * gk), (tt // gk) % g1, tt % gk

    def tile_copy(ii, jj, kk):
        cp_a = pltpu.make_async_copy(
            a_hbm.at[pl.ds(ii * _BM, _BM), pl.ds(kk * _BK, _BK)],
            a_vmem.at[0], sem_a.at[0])
        cp_b = pltpu.make_async_copy(
            b_hbm.at[pl.ds(kk * _BK, _BK), pl.ds(jj * _BN, _BN)],
            b_vmem.at[0], sem_b.at[0])
        return cp_a, cp_b

    @pl.when(t == 0)
    def _():
        ca, cb = tile_copy(i, j, k)
        ca.start()
        cb.start()

    # BROKEN: the next step's block is DMA'd into the only slot
    # *before* this step's compute has drained it
    @pl.when(jnp.logical_and(t > 0, t + 1 < total))
    def _():
        i_n, j_n, k_n = ijk_of(t + 1)
        ca, cb = tile_copy(i_n, j_n, k_n)
        ca.start()
        cb.start()

    ca, cb = tile_copy(i, j, k)
    ca.wait()
    cb.wait()
    prod = jnp.dot(a_vmem[0], b_vmem[0],
                   preferred_element_type=jnp.float32)

    @pl.when(k == 0)
    def _():
        acc[...] = prod

    @pl.when(k != 0)
    def _():
        acc[...] = acc[...] + prod

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        c_ref[...] = acc[...].astype(c_ref.dtype)


def _s1_overlap(a, b):
    gi, gj, gk = 2, 2, 2
    return pl.pallas_call(
        _s1_overlap_kernel,
        grid=(gi, gj, gk),
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
                  pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
        out_specs=pl.BlockSpec((_BM, _BN), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((16, 16), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((1, _BM, _BK), jnp.float32),
            pltpu.VMEM((1, _BK, _BN), jnp.float32),
            pltpu.VMEM((_BM, _BN), jnp.float32),
            pltpu.SemaphoreType.DMA((1,)),
            pltpu.SemaphoreType.DMA((1,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=True,
        name="zero_stall_matmul_s1_ijk",
    )(a, b)


def test_mutated_single_slot_overlap_flags_zs_k002():
    a = jnp.ones((16, 16), jnp.float32)
    (ir,) = trace_kernel_irs(_s1_overlap, a, a)
    # the mutant reuses the real kernel's name, so the declared
    # contract (and its slots=1 encoding) resolves against it
    assert ir.contract is not None and ir.contract.managed_dma
    report = lint_kernel_ir(ir)
    assert "ZS-K002" in report.rules(), report.format()
    assert any("in flight into the same slot" in d.message
               for d in report.errors if d.rule == "ZS-K002")


# ----------------------------------------------------------------------
# mutation C: aliased output overwrites a live input window
# ----------------------------------------------------------------------
def _aliased(a, *, in_map, out_map, name):
    return pl.pallas_call(
        lambda a_ref, o_ref: o_ref.__setitem__(..., a_ref[...] * 2.0),
        grid=(2,),
        in_specs=[pl.BlockSpec((8, 8), in_map)],
        out_specs=pl.BlockSpec((8, 8), out_map),
        out_shape=jax.ShapeDtypeStruct((16, 8), jnp.float32),
        input_output_aliases={0: 0},
        interpret=True,
        name=name,
    )(a)


def test_alias_overwriting_live_input_flags_zs_k005():
    a = jnp.ones((16, 8), jnp.float32)
    (ir,) = trace_kernel_irs(
        _aliased, a, in_map=lambda i: (0, 0), out_map=lambda i: (i, 0),
        name="mutant_alias_clobber")
    assert ir.input_output_aliases
    report = lint_kernel_ir(ir)
    assert "ZS-K005" in report.rules(), report.format()


def test_alias_disjoint_windows_is_clean():
    a = jnp.ones((16, 8), jnp.float32)
    (ir,) = trace_kernel_irs(
        _aliased, a, in_map=lambda i: (i, 0), out_map=lambda i: (i, 0),
        name="alias_in_place")
    report = lint_kernel_ir(ir)
    assert "ZS-K005" not in report.rules(), report.format()


# ----------------------------------------------------------------------
# mutation D: the matmul's row offset runs B's tiles past its stack
# ----------------------------------------------------------------------
_STACK_CFG = KernelConfig(backend="interpret", bm=8, bn=8, bk=8,
                          variant="dobu", slots=2)


def test_row_offset_past_the_stack_flags_zs_k006():
    a = jnp.ones((32, 32), jnp.float32)
    stack = jnp.ones((3, 32, 32), jnp.float32)
    (ir,) = trace_kernel_irs(ops.matmul, a, stack, layer=2,
                             config=_STACK_CFG)
    # the offset of the last layer, folded from the traced program
    assert ir.operand_values[2].tolist() == [64]
    assert not lint_kernel_ir(ir).errors
    report = lint_kernel_ir(dataclasses.replace(
        ir, operand_values={2: np.array([96], np.int32)}))
    assert "ZS-K006" in report.rules(), report.format()
    # an offset off the tile grid breaks the kernel's alignment promise
    report = lint_kernel_ir(dataclasses.replace(
        ir, operand_values={2: np.array([36], np.int32)}))
    assert any("multiple_of" in d.message for d in report.errors
               if d.rule == "ZS-K001"), report.format()


def test_traced_row_offset_needs_its_value():
    """A layer index traced by the caller leaves the offset unknown:
    the replay refuses to guess it, and checks it once supplied."""
    a = jnp.ones((32, 32), jnp.float32)
    stack = jnp.ones((3, 32, 32), jnp.float32)
    (ir,) = trace_kernel_irs(
        lambda a, s, i: ops.matmul(a, s, layer=i, config=_STACK_CFG),
        a, stack, jnp.int32(1))
    assert 2 not in ir.operand_values
    assert "ZS-K001" in lint_kernel_ir(ir).rules()
    ok = lint_kernel_ir(dataclasses.replace(
        ir, operand_values={2: np.array([32], np.int32)}))
    assert not ok.errors, ok.format()
