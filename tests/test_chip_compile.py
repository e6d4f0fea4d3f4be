"""The TPU compiler's verdict on the kernels of the serving path.

Every kernel is lowered and compiled by Mosaic for a *described* TPU
v5e (``jax.experimental.topologies``) — no chip attached, nothing runs.
That catches what interpret mode never sees: block shapes the tiling
rules refuse, and tiles that overflow the scoped VMEM a kernel asks
for.  Shapes are deepseek-coder-33b's published widths (d_model 7168,
d_ff 19200, 56 query / 8 KV heads of 128, vocab 32256) and
olmoe-1b-7b's expert widths (d_model 2048, expert d_ff 1024); tiles
are what the tuner picks for them, plus the largest candidate the
tuner's space admits per family.  About two seconds per compile.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import obs, tune
from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.grouped_matmul import grouped_zero_stall_matmul
from repro.kernels.quantized_matmul import (
    quantized_grouped_zero_stall_matmul,
    quantized_zero_stall_matmul,
)
from repro.kernels.zero_stall_matmul import zero_stall_matmul
from repro.models import Ctx, build_model
from repro.plan import KernelConfig
from repro.quant import QTensor
from repro.tune import DEFAULT_SPACE, Problem

D_MODEL, D_FF, VOCAB, HEADS, KV_HEADS, HEAD_DIM = 7168, 19200, 32256, 56, 8, 128
GEMMS = {"mlp_in": (D_FF, D_MODEL), "mlp_out": (D_MODEL, D_FF),
         "lm_head": (VOCAB, D_MODEL)}      # (N, K)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


@pytest.fixture
def fresh_tune_cache(tmp_path):
    """Tile picks from the code under test, never from a stale file."""
    prev = tune.get_cache()
    tune.set_cache(tune.TuneCache(tmp_path / "tune.json"))
    yield
    tune.set_cache(prev)


def _compile(fn, *args):
    """Lower + compile for the described chip; returns the executable
    (raises whatever Mosaic/XLA would raise on the chip)."""
    return jax.jit(fn).lower(*args).compile()


def _largest(dtype_bytes: int, op: str = "matmul"):
    """The admitted candidate with the biggest VMEM footprint."""
    problem = Problem(op, 4096, 4096, 4096, dtype_bytes=dtype_bytes)
    model = DEFAULT_SPACE.model
    return max(DEFAULT_SPACE.candidates(problem),
               key=lambda c: model.vmem_footprint(
                   c.bm, c.bn, c.bk, dtype_bytes=dtype_bytes,
                   slots=c.slots))


@pytest.mark.parametrize("gemm", sorted(GEMMS))
@pytest.mark.parametrize("M", [8, 2048], ids=["decode", "prefill"])
def test_matmul_tuner_pick_compiles(sds, fresh_tune_cache, M, gemm):
    N, K = GEMMS[gemm]
    cand = tune.best_config("matmul", M, N, K, dtype=jnp.bfloat16,
                            backend="pallas")
    cfg = KernelConfig.from_candidate(cand, backend="pallas")
    exe = _compile(lambda a, b: ops.matmul(a, b, config=cfg),
                   sds((M, K), jnp.bfloat16), sds((K, N), jnp.bfloat16))
    assert "tpu_custom_call" in exe.as_text()


def test_flash_attention_prefill_compiles(sds, fresh_tune_cache):
    B, S = 1, 2048
    bq, bkv = tune.best_attention_config(
        S, S, HEAD_DIM, dtype=jnp.bfloat16, backend="pallas",
        batch_heads=B * HEADS)
    cfg = KernelConfig(backend="pallas", bq=bq, bkv=bkv)
    qkv = sds((B, HEADS, S, HEAD_DIM), jnp.bfloat16)
    _compile(lambda q, k, v, n: ops.attention(q, k, v, config=cfg,
                                              q_lens=n, kv_lens=n),
             qkv, qkv, qkv, sds((B,), jnp.int32))


def test_paged_attention_gqa_pool_compiles(sds):
    """The (1, ps, 1, D) per-head page block was refused for KV > 1;
    the lane-folded (ps, D) block must compile at 8 KV heads."""
    B, ps, T = 8, 16, 32
    pool = sds((B * T + 1, ps, KV_HEADS, HEAD_DIM), jnp.bfloat16)
    cfg = KernelConfig(backend="pallas")
    _compile(lambda q, k, v, pt, n: ops.paged_attention(
                 q, k, v, pt, kv_lens=n, config=cfg),
             sds((B, HEADS, HEAD_DIM), jnp.bfloat16), pool, pool,
             sds((B, T), jnp.int32), sds((B,), jnp.int32))


def test_quantized_matmul_int8_pick_compiles(sds, fresh_tune_cache):
    M, (N, K) = 2048, GEMMS["mlp_in"]
    cand = tune.best_config("matmul", M, N, K, dtype=jnp.int8,
                            backend="pallas")
    cfg = KernelConfig.from_candidate(cand, backend="pallas")
    w = QTensor(sds((K, N), jnp.int8), sds((1, N), jnp.float32),
                fmt="int8", w8a8=True)
    _compile(lambda x, w: ops.quantized_matmul(x, w, config=cfg,
                                               out_dtype=jnp.bfloat16),
             sds((M, K), jnp.bfloat16), w)


@pytest.mark.parametrize("N,K", [(1024, 2048), (2048, 1024)],
                         ids=["expert_in", "expert_out"])
def test_grouped_matmul_olmoe_experts_compile(sds, fresh_tune_cache, N, K):
    G, M = 8, 512
    cand = tune.best_config("grouped_matmul", M, N, K, dtype=jnp.bfloat16,
                            backend="pallas", groups=G)
    cfg = KernelConfig.from_candidate(cand, backend="pallas")
    _compile(lambda a, b: ops.grouped_matmul(a, b, config=cfg),
             sds((G, M, K), jnp.bfloat16), sds((G, K, N), jnp.bfloat16))


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
def test_largest_admitted_matmul_tile_compiles(sds, dtype):
    """No candidate the space admits may be refused by the compiler —
    the budget and the kernels' vmem_limit_bytes are one number."""
    c = _largest(jnp.dtype(dtype).itemsize)
    M = N = K = 2 * max(c.bm, c.bn, c.bk)
    if dtype == "int8":
        fn = (lambda a, b, sa, sb: quantized_zero_stall_matmul(
            a, b, sa, sb, bm=c.bm, bn=c.bn, bk=c.bk, slots=c.slots,
            out_dtype=jnp.float32))
        args = (sds((M, K), jnp.int8), sds((K, N), jnp.int8),
                sds((M, 1), jnp.float32), sds((1, N), jnp.float32))
    else:
        fn = (lambda a, b: zero_stall_matmul(
            a, b, bm=c.bm, bn=c.bn, bk=c.bk, slots=c.slots,
            out_dtype=jnp.float32))
        args = (sds((M, K), jnp.bfloat16), sds((K, N), jnp.bfloat16))
    _compile(fn, *args)


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
def test_largest_admitted_grouped_tile_compiles(sds, dtype):
    c = _largest(jnp.dtype(dtype).itemsize, op="grouped_matmul")
    G, M = 2, 2 * c.bm
    N = K = 2 * max(c.bn, c.bk)
    if dtype == "int8":
        fn = (lambda a, b, sa, sb: quantized_grouped_zero_stall_matmul(
            a, b, sa, sb, bm=c.bm, bn=c.bn, bk=c.bk, slots=c.slots,
            out_dtype=jnp.float32))
        args = (sds((G, M, K), jnp.int8), sds((G, K, N), jnp.int8),
                sds((G, M, 1), jnp.float32), sds((G, 1, N), jnp.float32))
    else:
        fn = (lambda a, b: grouped_zero_stall_matmul(
            a, b, bm=c.bm, bn=c.bn, bk=c.bk, slots=c.slots,
            out_dtype=jnp.float32))
        args = (sds((G, M, K), jnp.bfloat16), sds((G, K, N), jnp.bfloat16))
    _compile(fn, *args)


def test_largest_admitted_attention_tile_compiles(sds):
    space = DEFAULT_SPACE
    bq, bkv = max(((q, k) for q in space.tile_options
                   for k in space.tile_options
                   if space.fits_vmem_attention(q, k, HEAD_DIM)),
                  key=lambda t: t[0] * t[1])
    S = 2 * max(bq, bkv)
    qkv = sds((1, HEADS, S, HEAD_DIM), jnp.bfloat16)
    cfg = KernelConfig(backend="pallas", bq=bq, bkv=bkv)
    _compile(lambda q, k, v: ops.attention(q, k, v, config=cfg),
             qkv, qkv, qkv)


def _with_operand_shapes(hlo: str) -> str:
    """The optimized HLO with each operand written as ``shape %name``,
    the form in which the TPU profiler names an event."""
    shapes = dict(re.findall(r"^\s*(?:ROOT )?(%\S+) = (\S+) ", hlo, re.M))
    return re.sub(r"(?<=[(,] )(%[\w.\-]+)|(?<=\()(%[\w.\-]+)",
                  lambda m: f"{shapes.get(m.group(0), '')} {m.group(0)}",
                  hlo)


def test_decode_step_reads_layer_weights_in_place(sds, fresh_tune_cache):
    """A 2-layer deepseek-width decode step: every weight the tuner's
    tiles divide reaches its ``zero_stall_matmul`` as a bitcast of the
    (L*K, N) stack, never through a copy; the two that ``bn`` = 512
    would pad (d_ff 19200) keep the slice.  The benchmark's roofline
    reader attributes each in-place call to the problem recorded."""
    from chipbench.trace import matmul_call, op_name, operand_shapes
    cfg = dataclasses.replace(get_config("deepseek-coder-33b"), n_layers=2)
    model = build_model(cfg)
    ctx = Ctx(plan="pallas", dtype=jnp.bfloat16)

    def described(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)
    params = described(jax.eval_shape(
        lambda k: model.init(k, dtype=jnp.bfloat16), jax.random.PRNGKey(0)))
    cache = described(jax.eval_shape(
        lambda: model.init_cache(8, 256, jnp.bfloat16)))
    with obs.capture():
        exe = _compile(lambda p, c, t: model.decode(p, c, t, ctx), params,
                       cache, sds((8, 1), jnp.int32))
        records = [(r.M, r.N, r.K, r.config.bm, r.config.bn, r.config.bk)
                   for r in obs.recorded_ops() if r.op == "matmul"]
    hlo = _with_operand_shapes(exe.as_text())
    lines = [ln.strip() for ln in hlo.splitlines() if " = " in ln]
    defs = {ln.split(" = ")[0].removeprefix("ROOT "): ln for ln in lines}

    in_place = []
    for ln in lines:
        if not op_name(ln).startswith("zero_stall_matmul"):
            continue
        _, ((_, (_, k_pad)), (_, (rows, _)), *_) = operand_shapes(ln)
        if rows == k_pad:
            continue                      # a 2-D weight or a slice
        call = matmul_call(ln, records)
        assert (call.M, call.N, call.K) in {r[:3] for r in records}
        assert rows == 2 * call.K
        in_place.append((call.N, call.K))
        src = re.search(r"custom-call\([^%]*%\S+, [^%]*(%[\w.\-]+)",
                        ln).group(1)
        while " bitcast(" in defs[src]:
            src = re.search(r"bitcast\([^%]*(%[\w.\-]+)", defs[src]).group(1)
        assert re.search(r" (get-tuple-element|parameter)\(", defs[src]), \
            defs[src]
    assert sorted(in_place) == sorted([(7168, 7168), (1024, 7168),
                                       (1024, 7168), (7168, 7168),
                                       (7168, 19200)])
    copies = {(K, N): [ln for ln in lines
                       if f"bf16[2,{K},{N}]" in ln.split(" = ", 1)[1]
                       and re.search(r" (fusion|copy|dynamic-slice)\(", ln)]
              for K, N in [(7168, 7168), (7168, 1024), (19200, 7168),
                           (7168, 19200)]}
    assert not any(copies[k] for k in [(7168, 7168), (7168, 1024),
                                       (19200, 7168)]), copies
    assert copies[(7168, 19200)]          # wi and wg are still sliced
