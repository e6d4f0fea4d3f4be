"""Kernel allclose sweeps vs the pure-jnp oracles (interpret mode).

Every Pallas kernel is executed with interpret=True (the kernel body —
including the manual DMA revolving buffer — runs in Python on CPU) and
compared against ref.py across shapes, dtypes and both pipeline
variants.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.plan import KernelConfig
from repro.kernels.zero_stall_matmul import zero_stall_matmul
from repro.kernels.grouped_matmul import grouped_zero_stall_matmul


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("variant", ["dobu", "single"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mkn,tiles", [
    ((16, 16, 16), (8, 8, 8)),
    ((32, 48, 16), (16, 16, 16)),
    ((24, 16, 40), (8, 8, 8)),
    ((8, 64, 8), (8, 8, 8)),
])
def test_zero_stall_matmul(rng, mkn, tiles, dtype, variant):
    M, K, N = mkn
    bm, bn, bk = tiles
    a = jnp.asarray(rng.standard_normal((M, K)), dtype)
    b = jnp.asarray(rng.standard_normal((K, N)), dtype)
    got = zero_stall_matmul(a, b, bm=bm, bn=bn, bk=bk, variant=variant,
                            interpret=True)
    want = ref.matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_zero_stall_matmul_rejects_ragged(rng):
    a = jnp.zeros((12, 16), jnp.float32)
    b = jnp.zeros((16, 16), jnp.float32)
    with pytest.raises(ValueError):
        zero_stall_matmul(a, b, bm=8, bn=8, bk=8, interpret=True)


def test_ops_matmul_pads_ragged(rng):
    a = jnp.asarray(rng.standard_normal((13, 21)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((21, 9)), jnp.float32)
    got = ops.matmul(a, b, config=KernelConfig(backend="interpret",
                                               bm=8, bn=8, bk=8))
    np.testing.assert_allclose(got, ref.matmul_ref(a, b), atol=2e-5)


@pytest.mark.parametrize("variant", ["dobu", "single"])
@pytest.mark.parametrize("g,mkn", [(1, (8, 8, 8)), (3, (16, 24, 16)),
                                   (5, (8, 16, 8))])
def test_grouped_matmul(rng, g, mkn, variant):
    M, K, N = mkn
    a = jnp.asarray(rng.standard_normal((g, M, K)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((g, K, N)), jnp.float32)
    got = grouped_zero_stall_matmul(a, b, bm=8, bn=8, bk=8,
                                    variant=variant, interpret=True)
    np.testing.assert_allclose(got, ref.grouped_matmul_ref(a, b), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s,d,bq,bkv", [(32, 16, 8, 8), (64, 32, 16, 16),
                                        (32, 8, 32, 8)])
def test_flash_attention(rng, s, d, bq, bkv, causal):
    q = jnp.asarray(rng.standard_normal((2, 2, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 2, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 2, s, d)), jnp.float32)
    got = ops.attention(q, k, v, causal=causal,
                        config=KernelConfig(backend="interpret",
                                            bq=bq, bkv=bkv))
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


def test_host_tiled_matmul_matches(rng):
    """The pre-ZONL baseline is numerically identical — only slower."""
    a = jnp.asarray(rng.standard_normal((32, 32)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((32, 32)), jnp.float32)
    got = ops.host_tiled_matmul(a, b, bm=8, bn=8, bk=8)
    np.testing.assert_allclose(got, ref.matmul_ref(a, b), atol=1e-4)


def test_dispatch_jnp_path(rng):
    a = jnp.asarray(rng.standard_normal((4, 4)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((4, 4)), jnp.float32)
    assert ops.resolve_impl("auto") == "jnp"    # CPU container
    np.testing.assert_allclose(ops.matmul(a, b),
                               ref.matmul_ref(a, b), atol=1e-6)


_LAYER_TILES = KernelConfig(backend="interpret", bm=8, bn=8, bk=8)


@pytest.mark.parametrize("M", [1, 8, 200])
@pytest.mark.parametrize("layer", [0, 1, 2, -4, -1, 3, 7])
def test_matmul_reads_a_stacked_layer_in_place(rng, layer, M):
    """``matmul(a, stack, layer=i)`` DMAs layer i's tiles out of the
    stack and gives the bits of ``matmul(a, stack[i])``; an index
    outside the stack reads the layer ``lax.dynamic_index_in_dim``
    reads (negative from the end, then clamped)."""
    stack = jnp.asarray(rng.standard_normal((3, 32, 24)), jnp.bfloat16)
    a = jnp.asarray(rng.standard_normal((M, 32)), jnp.bfloat16)
    want = ops.matmul(
        a, jax.lax.dynamic_index_in_dim(stack, layer, keepdims=False),
        config=_LAYER_TILES)
    got = ops.matmul(a, stack, layer=layer, config=_LAYER_TILES)
    traced = jax.jit(lambda a, s, i: ops.matmul(
        a, s, layer=i, config=_LAYER_TILES))(a, stack, jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(traced, np.float32),
                                  np.asarray(want, np.float32))
    counts = ops.layer_weight_counts()
    assert counts["in_place.calls"] == 2 and counts["sliced.calls"] == 0
    assert counts["in_place.bytes"] == 2 * 32 * 24 * 2


@pytest.mark.parametrize("config", [
    KernelConfig(backend="interpret", bm=8, bn=16, bk=8),
    KernelConfig(backend="jnp")], ids=["tiles_pad_N", "jnp"])
def test_matmul_slices_a_stacked_layer_it_cannot_read_in_place(rng,
                                                               config):
    """N = 24 is no multiple of bn = 16 (the weight would be padded),
    and the jnp backend has no kernel: both slice the layer out."""
    stack = jnp.asarray(rng.standard_normal((3, 32, 24)), jnp.float32)
    a = jnp.asarray(rng.standard_normal((5, 32)), jnp.float32)
    got = ops.matmul(a, stack, layer=1, config=config)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ops.matmul(a, stack[1], config=config)))
    assert ops.layer_weight_counts() == {
        "in_place.calls": 0, "in_place.bytes": 0,
        "sliced.calls": 1, "sliced.bytes": 32 * 24 * 4}


def test_zero_stall_matmul_row_offset(rng):
    """The kernel's row offset: B rows ``row0 .. row0 + K``; a B that
    holds no whole number of K-row matrices is refused."""
    a = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((48, 8)), jnp.float32)
    got = zero_stall_matmul(a, b, 32, bm=8, bn=8, bk=8, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(zero_stall_matmul(a, b[32:], bm=8, bn=8, bk=8,
                                     interpret=True)))
    with pytest.raises(ValueError, match="contraction mismatch"):
        zero_stall_matmul(a, b[:40], 0, bm=8, bn=8, bk=8, interpret=True)
