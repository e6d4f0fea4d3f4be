"""Model building blocks (pure functional JAX).

Every matmul routes through :mod:`repro.kernels.ops`, so the paper's
zero-stall engine is the compute path on TPU while the dry-run lowers
the identical-math jnp path (DESIGN.md §3).  Params are plain nested
dicts (pytrees); init fns return params, apply fns are pure.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any

import jax
import jax.numpy as jnp

from repro import plan as _plan
from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.plan import UNSET as _UNSET
from repro.quant.tensor import QTensor

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Ctx:
    """Per-call execution context.

    ``plan`` is the one execution-configuration field
    (:mod:`repro.plan`): a backend string ("auto" | "jnp" | "pallas" |
    "interpret", with auto-tuned kernel configs), a
    :class:`~repro.plan.KernelConfig` (one fixed config everywhere), a
    :class:`~repro.plan.Plan` (per-call-site table, e.g. from
    :func:`repro.plan.trace_model`), a tile tuple, or ``None`` (the
    historical fixed 128³ default).  It is normalized to a ``Plan`` at
    construction; quantized execution is the plan's ``quant`` field.

    The pre-plan ``impl=``/``tiling=``/``quant=`` keywords still
    construct (deprecated, one ``DeprecationWarning``) and remain
    readable as attributes, derived from the plan.
    """
    plan: Any = "auto"            # execution plan (repro.plan vocabulary)
    dtype: Any = jnp.bfloat16     # compute dtype
    decode: bool = False
    mesh: Any = None              # when set, activation sharding constraints
                                  # (sequence parallelism) are applied

    # impl/tiling/quant are keyword-only constructor shims + derived
    # read-only properties, NOT dataclass fields: dataclasses.replace()
    # therefore round-trips on the real fields alone, so
    # replace(ctx, plan=other) can never conflict with stale derived
    # values.
    def __init__(self, plan: Any = "auto", dtype: Any = jnp.bfloat16,
                 decode: bool = False, mesh: Any = None, *,
                 impl: Any = _UNSET, tiling: Any = _UNSET,
                 quant: Any = _UNSET):
        legacy = {n: v for n, v in
                  (("impl", impl), ("tiling", tiling), ("quant", quant))
                  if v is not _UNSET}
        if legacy:
            if isinstance(plan, _plan.Plan) or plan != "auto":
                raise ValueError(
                    f"Ctx: cannot combine plan= with the deprecated "
                    f"{sorted(legacy)} keyword(s); set the value on the "
                    f"plan instead")
            warnings.warn(
                "Ctx(impl=, tiling=, quant=) is deprecated; pass "
                "Ctx(plan=...) — a backend string, KernelConfig, Plan, "
                "tile tuple or None (see repro.plan)",
                DeprecationWarning, stacklevel=2)
            p = _plan.Plan.from_legacy(impl=legacy.get("impl", "auto"),
                                       tiling=legacy.get("tiling", "auto"),
                                       quant=legacy.get("quant"))
        else:
            p = _plan.as_plan(plan)
        object.__setattr__(self, "plan", p)
        object.__setattr__(self, "dtype", dtype)
        object.__setattr__(self, "decode", decode)
        object.__setattr__(self, "mesh", mesh)

    @property
    def impl(self) -> str:
        """Deprecated read: the plan's backend."""
        return self.plan.backend

    @property
    def tiling(self):
        """Deprecated read: the plan's default policy, old vocabulary."""
        return self.plan.legacy_tiling()

    @property
    def quant(self):
        """Deprecated read: the plan's quantized-execution mode."""
        return self.plan.quant

    def with_plan(self, plan) -> "Ctx":
        """This context with a different execution plan."""
        return Ctx(plan=plan, dtype=self.dtype, decode=self.decode,
                   mesh=self.mesh)


def shard_seq(x: jax.Array, ctx: "Ctx") -> jax.Array:
    """Sequence-parallel constraint on a (B, S, d) activation.

    Applied at layer boundaries AND on the attention/MLP block outputs:
    the output-side constraint is what makes GSPMD emit the Megatron
    reduce-scatter form at the TP boundary instead of a full-activation
    all-reduce followed by a slice (measured: the AR form costs ~16x
    the RS bytes on deepseek train_4k).
    """
    if ctx.mesh is None or x.ndim != 3:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    names = ctx.mesh.axis_names
    sizes = dict(zip(names, ctx.mesh.devices.shape))
    dp = tuple(a for a in ("pod", "data") if a in names)
    dp_size = 1
    for a in dp:
        dp_size *= sizes[a]
    b_ax = dp if dp_size > 1 and x.shape[0] >= dp_size else None
    s_ax = ("model" if "model" in names and x.shape[1] >= sizes["model"]
            else None)
    if b_ax is None and s_ax is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, P(b_ax, s_ax, None)))


def shard_act(x: jax.Array, ctx: "Ctx") -> jax.Array:
    """Sequence-parallel activation constraint at layer boundaries.

    Residual activations (B, S, d) are the dominant live state of the
    backward pass (one per layer under the scan).  Sharding batch over
    the DP axes and *sequence over the 'model' axis* (Megatron-style SP
    — GSPMD inserts the all-gather before attention and the
    reduce-scatter after) cuts that term by the model-axis size.
    """
    y = shard_seq(x, ctx)
    if y is x:
        return x
    # Pin the carry at the layer boundary: without this, XLA hoists the
    # fp32 upcast of the *whole* (layers, B, S, d) saved-residual stack
    # out of the backward loop (measured: +16.5 GiB/device on
    # mistral-large-123b).  The barrier keeps per-layer slices inside.
    return jax.lax.optimization_barrier(y)


# ----------------------------------------------------------------------
# init helpers
# ----------------------------------------------------------------------
def _dense_init(key, d_in: int, d_out: int, dtype=jnp.float32) -> jax.Array:
    scale = d_in ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, (d_in, d_out),
                                        jnp.float32) * scale).astype(dtype)


def init_linear(key, d_in: int, d_out: int, *, bias: bool = False,
                dtype=jnp.float32) -> Params:
    p = {"w": _dense_init(key, d_in, d_out, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def _kernels_on_mesh(ctx: "Ctx | None") -> bool:
    """Pallas calls under a multi-device mesh need an explicit
    ``shard_map``: Mosaic kernels cannot be partitioned by GSPMD."""
    return (ctx is not None and ctx.mesh is not None and ctx.mesh.size > 1
            and ops.resolve_impl(ctx.plan.backend) in ("pallas", "interpret"))


def _model_split(mesh, dim: int) -> str | None:
    """'model' when that axis exists and divides ``dim``, else None."""
    tp = dict(mesh.shape).get("model", 1)
    return "model" if tp > 1 and dim % tp == 0 else None


def _matmul(x2: jax.Array, w: jax.Array, ctx: Ctx, *, out_dtype,
            split: str | None = None) -> jax.Array:
    """``ops.matmul``; under a mesh with kernels, Megatron tensor
    parallelism through ``shard_map``: ``split="col"`` keeps each
    chip's slice of w's output columns (output sharded the same way),
    ``split="row"`` contracts each chip's slice of the input features
    and sums the float32 partial products over 'model'.  Anything
    unsplit runs replicated."""
    if not _kernels_on_mesh(ctx):
        return ops.matmul(x2, w, config=ctx.plan, out_dtype=out_dtype)
    from jax.sharding import PartitionSpec as P
    col = split == "col" and _model_split(ctx.mesh, w.shape[1])
    row = split == "row" and _model_split(ctx.mesh, w.shape[0])
    specs = ((P(), P(None, "model"), P(None, "model")) if col else
             (P(None, "model"), P("model", None), P()) if row else
             (P(), P(), P()))

    def local(x, w):
        if not row:
            return ops.matmul(x, w, config=ctx.plan, out_dtype=out_dtype)
        part = ops.matmul(x, w, config=ctx.plan, out_dtype=jnp.float32)
        return jax.lax.psum(part, "model").astype(out_dtype)
    return jax.shard_map(local, mesh=ctx.mesh, in_specs=specs[:2],
                         out_specs=specs[2], check_vma=False)(x2, w)


@dataclasses.dataclass(frozen=True)
class StackedWeight:
    """Layer ``layer`` of a stacked (L, K, N) linear weight, left in the
    stack.  :func:`linear` hands both to ``ops.matmul(..., layer=)``,
    which lets the kernel read the layer's tiles in place instead of
    copying the layer out of the stack first."""

    stack: jax.Array
    layer: jax.Array

    @property
    def shape(self) -> tuple:
        return tuple(self.stack.shape[1:])


def layer_params(stacked: Params, layer: jax.Array, ctx: Ctx, *,
                 sliced: tuple[str, ...] = ()) -> Params:
    """Layer ``layer`` of the scan-stacked parameter tree.

    Every linear weight (``{"w": (L, K, N)}``) that is a plain array in
    the compute dtype, with no mesh to split it, stays in its stack as
    a :class:`StackedWeight`.  Everything else — norm scales, biases,
    quantized weights, the top-level subtrees named in ``sliced`` — is
    sliced at ``layer``, as ``lax.scan`` slices its ``xs``."""
    def take(x):
        return jax.lax.dynamic_index_in_dim(x, layer, keepdims=False)

    def walk(node):
        if not isinstance(node, dict):
            return jax.tree.map(take, node)
        return {k: (StackedWeight(v, layer) if k == "w" and keep(v)
                    else walk(v)) for k, v in node.items()}

    def keep(w):
        return (isinstance(w, jax.Array) and w.ndim == 3
                and w.dtype == ctx.dtype and not _kernels_on_mesh(ctx))

    return {k: jax.tree.map(take, v) if k in sliced else walk(v)
            for k, v in stacked.items()}


def linear(p: Params, x: jax.Array, ctx: Ctx, *,
           split: str | None = None) -> jax.Array:
    """x: (..., d_in) @ w -> (..., d_out) through the zero-stall engine.

    :class:`~repro.quant.QTensor` weights (``Model.quantize_weights``)
    dispatch by ``ctx.plan.quant``: ``"int8"`` runs the W8A8
    zero-stall kernel (dynamic per-row activation quantization, fused
    dequant epilogue); anything else dequantizes the weight on the fly
    and runs the standard kernel — so fp8-simulated and opted-out
    quantized params still execute on the Pallas path, never a jnp
    fallback.  ``split`` ("col" | "row") is this layer's place in
    Megatron tensor parallelism (see :func:`_matmul`).  A
    :class:`StackedWeight` (from :func:`layer_params`) goes to the
    kernel with its stack and layer index.
    """
    w = p["w"]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if isinstance(w, StackedWeight):
        y = ops.matmul(x2, w.stack, layer=w.layer, config=ctx.plan,
                       out_dtype=ctx.dtype)
        d_out = w.shape[-1]
    elif isinstance(w, QTensor):
        if ctx.plan.quant == "int8" and w.fmt == "int8" and w.w8a8:
            y = ops.quantized_matmul(x2, w, config=ctx.plan,
                                     out_dtype=ctx.dtype)
        else:
            y = ops.matmul(x2, w.dequantize(ctx.dtype), config=ctx.plan,
                           out_dtype=ctx.dtype)
        d_out = w.shape[-1]
    else:
        w = w.astype(ctx.dtype)
        y = _matmul(x2, w, ctx, out_dtype=ctx.dtype, split=split)
        d_out = w.shape[-1]
    y = y.reshape(*lead, d_out)
    if "b" in p:
        y = y + p["b"].astype(ctx.dtype)
    return y


# ----------------------------------------------------------------------
# norms
# ----------------------------------------------------------------------
def init_rmsnorm(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rms_norm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


# ----------------------------------------------------------------------
# rotary embeddings
# ----------------------------------------------------------------------
def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) or (S,)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


# ----------------------------------------------------------------------
# attention (GQA / MQA, optional QKV bias)
# ----------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": init_linear(ks[0], d, cfg.n_heads * hd, bias=cfg.qkv_bias,
                          dtype=dtype),
        "wk": init_linear(ks[1], d, cfg.n_kv_heads * hd,
                          bias=cfg.qkv_bias, dtype=dtype),
        "wv": init_linear(ks[2], d, cfg.n_kv_heads * hd,
                          bias=cfg.qkv_bias, dtype=dtype),
        "wo": init_linear(ks[3], cfg.n_heads * hd, d, dtype=dtype),
    }


def _qkv(p: Params, x: jax.Array, cfg: ModelConfig, ctx: Ctx):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = linear(p["wq"], x, ctx, split="col").reshape(B, S, cfg.n_heads, hd)
    k = linear(p["wk"], x, ctx, split="col").reshape(B, S, cfg.n_kv_heads,
                                                     hd)
    v = linear(p["wv"], x, ctx, split="col").reshape(B, S, cfg.n_kv_heads,
                                                     hd)
    return q, k, v


# chunk threshold: materialize S x T scores only below this element count
_ATTN_CHUNK_ELEMS = 1024 * 1024
_Q_CHUNK = 1024
_KV_CHUNK = 1024


def _head_shard(t: jax.Array, ctx: "Ctx | None") -> jax.Array:
    """Constrain a (B, S, H, D) attention tensor to head-TP layout.

    Without this GSPMD may split the score einsum over the contraction
    dim and emit partial-sum all-reduces of the scores (measured 96 s
    collective term on deepseek train_4k; §Perf-2)."""
    if ctx is None or ctx.mesh is None or "model" not in ctx.mesh.axis_names:
        return t
    from jax.sharding import NamedSharding, PartitionSpec as P
    sizes = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))
    if t.shape[2] % sizes["model"] != 0:
        return t
    dp = tuple(a for a in ("pod", "data") if a in ctx.mesh.axis_names)
    dps = 1
    for a in dp:
        dps *= sizes[a]
    b_ax = dp if t.shape[0] % dps == 0 and dps > 1 else None
    return jax.lax.with_sharding_constraint(
        t, NamedSharding(ctx.mesh, P(b_ax, None, "model", None)))


def _seq_shard4(t: jax.Array, ctx: "Ctx | None") -> jax.Array:
    """Pin a (B, S, KV, D) tensor to the SP layout (S over 'model').

    Applied to k/v BEFORE the head repeat: otherwise the head-layout
    demand on the repeat output propagates into its 8-KV-head input,
    which cannot shard 16-way — GSPMD falls back to involuntary full
    rematerialization (measured 592 s collective term on the multi-pod
    mistral train cell).  With the input pinned, the repeat runs local
    and the S<->H transpose happens on the clean 96-head output.
    """
    if ctx is None or ctx.mesh is None or "model" not in ctx.mesh.axis_names:
        return t
    from jax.sharding import NamedSharding, PartitionSpec as P
    sizes = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))
    if t.shape[1] % sizes["model"] != 0:
        return t
    dp = tuple(a for a in ("pod", "data") if a in ctx.mesh.axis_names)
    dps = 1
    for a in dp:
        dps *= sizes[a]
    b_ax = dp if t.shape[0] % dps == 0 and dps > 1 else None
    return jax.lax.with_sharding_constraint(
        t, NamedSharding(ctx.mesh, P(b_ax, "model", None, None)))


def _lengths_mask(S: int, T: int, lengths: jax.Array, causal: bool,
                  offsets: jax.Array | None = None) -> jax.Array:
    """(B, S, T) validity mask for per-sequence valid lengths.

    Positions are absolute indices (query row i == position
    ``offsets[b] + i``, offsets defaulting to zero), matching the
    Pallas kernel's variable-length convention."""
    rows = jnp.arange(S)[:, None]
    if offsets is not None:
        rows = rows[None] + offsets[:, None, None]       # (B, S, 1)
    cols = jnp.arange(T)[None, :]
    m = ((rows < lengths[:, None, None]) & (cols < lengths[:, None, None]))
    if causal:
        m = m & (rows >= cols)
    return jnp.broadcast_to(m, (lengths.shape[0], S, T))


def _attn_config(config, impl: str):
    """ops.attention config for an already-resolved backend.

    Plans and KernelConfigs carry their own backend; the bare-string /
    tuple / None vocabulary gets ``impl`` folded in so both dispatch
    decisions (here and inside ops.attention) agree."""
    if isinstance(config, (_plan.Plan, _plan.KernelConfig)):
        return config
    if config == "auto":
        return _plan.Plan(backend=impl)
    if config is None:
        return _plan.KernelConfig(backend=impl)
    if isinstance(config, (tuple, list)) and len(config) == 2:
        return _plan.KernelConfig(backend=impl, bq=int(config[0]),
                                  bkv=int(config[1]))
    return config


def _gqa_full(q, k, v, *, causal: bool, impl: str,
              ctx: "Ctx | None" = None, config="auto",
              lengths: jax.Array | None = None,
              q_offset: jax.Array | None = None, mesh=None) -> jax.Array:
    """q: (B,S,H,D), k/v: (B,T,KV,D) -> (B,S,H,D).

    Under a mesh, KV heads are repeated up to H ("merged-head" form) so
    the single head dim shards cleanly over the 16-way 'model' axis —
    at train/prefill sizes the repeated K/V cost is trivial per device
    (T*H*D/16 elements), while the grouped (KV, rep) form cannot express
    a 16-way sharding across its two small head dims and forces GSPMD
    into score all-reduces.  Decode keeps the unrepeated form (the KV
    cache dominates there).

    ``lengths``: optional (B,) per-sequence valid lengths (ragged
    serving batches); rows/cols at >= length are masked, fully-masked
    rows produce zeros.  ``q_offset``: optional (B,) absolute position
    of query row 0 (chunked prefill — requires ``lengths``).  On the
    Pallas path this stays on the kernel via its length/offset
    operands — head-parallel through ``shard_map`` under a
    multi-device ``mesh`` — and on the jnp path the score mask gains a
    batch dimension (the chunked variants are skipped — serving
    prompts are far below the chunk threshold).
    """
    B, S, H, D = q.shape
    KV = k.shape[2]
    rep = H // KV
    T = k.shape[1]
    if impl in ("pallas", "interpret"):
        # flash kernel wants (B, H, S, D) with matched head counts
        kr = jnp.repeat(k, rep, axis=2).transpose(0, 2, 1, 3)
        vr = jnp.repeat(v, rep, axis=2).transpose(0, 2, 1, 3)

        def attend(q, k, v, lens, off):
            return ops.attention(q, k, v, config=_attn_config(config, impl),
                                 causal=causal, q_lens=lens, kv_lens=lens,
                                 q_offsets=off)
        if mesh is not None and mesh.size > 1:
            from jax.sharding import PartitionSpec as P
            heads = P(None, _model_split(mesh, H), None, None)
            attend = jax.shard_map(attend, mesh=mesh,
                                   in_specs=(heads, heads, heads, P(), P()),
                                   out_specs=heads, check_vma=False)
        o = attend(q.transpose(0, 2, 1, 3), kr, vr, lengths, q_offset)
        return o.transpose(0, 2, 1, 3)
    # merged-head path (callers gate via _merged_head_plan):
    if ctx is not None and ctx.mesh is not None:
        kr = _head_shard(jnp.repeat(k, rep, axis=2), ctx)
        vr = _head_shard(jnp.repeat(v, rep, axis=2), ctx)
        q = _head_shard(q, ctx)
        if (lengths is None and S * T > _ATTN_CHUNK_ELEMS
                and S % _Q_CHUNK == 0 and T % _KV_CHUNK == 0):
            return _mha_chunked(q, kr, vr, causal=causal)
        logits = jnp.einsum("bshd,bthd->bhst", q, kr,
                            preferred_element_type=jnp.float32) * (D ** -0.5)
        if lengths is not None:
            m = _lengths_mask(S, T, lengths, causal, q_offset)
            logits = jnp.where(m[:, None], logits, -1e30)
        elif causal:
            mask = jnp.tril(jnp.ones((S, T), bool), k=T - S)
            logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhst,bthd->bshd", probs.astype(vr.dtype), vr)
        if lengths is not None:
            out = jnp.where(m.any(-1)[:, :, None, None], out, 0)
        return out
    if (lengths is None and S * T > _ATTN_CHUNK_ELEMS
            and S % _Q_CHUNK == 0 and T % _KV_CHUNK == 0):
        return _gqa_chunked(q, k, v, causal=causal)
    # native grouped einsum (no kv-head materialization)
    qg = q.reshape(B, S, KV, rep, D)
    logits = jnp.einsum("bskrd,btkd->bkrst", qg, k,
                        preferred_element_type=jnp.float32) * (D ** -0.5)
    if lengths is not None:
        m = _lengths_mask(S, T, lengths, causal, q_offset)
        logits = jnp.where(m[:, None, None], logits, -1e30)
    elif causal:
        mask = jnp.tril(jnp.ones((S, T), bool), k=T - S)
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkrst,btkd->bskrd", probs.astype(v.dtype), v)
    if lengths is not None:
        o = jnp.where(m.any(-1)[:, :, None, None, None], o, 0)
    return o.reshape(B, S, H, D)


def _mha_chunked(q, k, v, *, causal: bool,
                 q_chunk: int = _Q_CHUNK, kv_chunk: int = _KV_CHUNK
                 ) -> jax.Array:
    """Merged-head blockwise attention (q/k/v all (B, S|T, H, D))."""
    B, S, H, D = q.shape
    T = k.shape[1]
    nq, nkv = S // q_chunk, T // kv_chunk
    scale = D ** -0.5
    qg = q.reshape(B, nq, q_chunk, H, D)

    def q_block(qi, q_blk):
        m0 = jnp.full((B, H, q_chunk, 1), -1e30, jnp.float32)
        l0 = jnp.zeros((B, H, q_chunk, 1), jnp.float32)
        a0 = jnp.zeros((B, H, q_chunk, D), jnp.float32)

        def kv_step(carry, kj):
            m, den, acc = carry
            k_blk = jax.lax.dynamic_slice_in_dim(k, kj * kv_chunk,
                                                 kv_chunk, 1)
            v_blk = jax.lax.dynamic_slice_in_dim(v, kj * kv_chunk,
                                                 kv_chunk, 1)
            s = jnp.einsum("bqhd,bthd->bhqt", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                rows = qi * q_chunk + jnp.arange(q_chunk)[:, None]
                cols = kj * kv_chunk + jnp.arange(kv_chunk)[None, :]
                s = jnp.where((rows >= cols)[None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            den_new = alpha * den + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum(
                "bhqt,bthd->bhqd", p.astype(v.dtype), v_blk,
                preferred_element_type=jnp.float32)
            return (m_new, den_new, acc_new), None

        (m, den, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                        jnp.arange(nkv))
        out = acc / jnp.where(den == 0.0, 1.0, den)
        return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B,qc,H,D)

    def outer(_, qi):
        return None, q_block(qi, qg[:, qi])

    q_block = jax.checkpoint(
        q_block, policy=jax.checkpoint_policies.nothing_saveable)
    _, blocks = jax.lax.scan(outer, None, jnp.arange(nq))
    return blocks.transpose(1, 0, 2, 3, 4).reshape(B, S, H, D)


def _gqa_chunked(q, k, v, *, causal: bool,
                 q_chunk: int = _Q_CHUNK, kv_chunk: int = _KV_CHUNK
                 ) -> jax.Array:
    """Flash-style blockwise attention for the jnp (dry-run/XLA) path.

    Never materializes the (S, T) score matrix: double scan over
    q-chunks (outer, rematerialized) and kv-chunks (inner, online
    softmax).  This is the XLA transcription of the Pallas
    flash_attention kernel — same dobu idea: stream kv tiles through a
    small working set instead of allocating the full score buffer.
    """
    B, S, H, D = q.shape
    KV = k.shape[2]
    rep = H // KV
    T = k.shape[1]
    nq, nkv = S // q_chunk, T // kv_chunk
    scale = D ** -0.5
    qg = q.reshape(B, nq, q_chunk, KV, rep, D)

    def q_block(qi, q_blk):
        """q_blk: (B, qc, KV, rep, D) -> attended output block."""
        m0 = jnp.full((B, KV, rep, q_chunk, 1), -1e30, jnp.float32)
        l0 = jnp.zeros((B, KV, rep, q_chunk, 1), jnp.float32)
        a0 = jnp.zeros((B, KV, rep, q_chunk, D), jnp.float32)

        def kv_step(carry, kj):
            m, den, acc = carry
            k_blk = jax.lax.dynamic_slice_in_dim(k, kj * kv_chunk,
                                                 kv_chunk, 1)
            v_blk = jax.lax.dynamic_slice_in_dim(v, kj * kv_chunk,
                                                 kv_chunk, 1)
            s = jnp.einsum("bqkrd,btkd->bkrqt", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                rows = qi * q_chunk + jnp.arange(q_chunk)[:, None]
                cols = kj * kv_chunk + jnp.arange(kv_chunk)[None, :]
                s = jnp.where((rows >= cols)[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            den_new = alpha * den + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum(
                "bkrqt,btkd->bkrqd", p.astype(v.dtype), v_blk,
                preferred_element_type=jnp.float32)
            return (m_new, den_new, acc_new), None

        (m, den, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                        jnp.arange(nkv))
        out = acc / jnp.where(den == 0.0, 1.0, den)
        # cast before stacking: the outer scan materializes these blocks
        return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)

    q_block = jax.checkpoint(
        q_block, policy=jax.checkpoint_policies.nothing_saveable)

    def outer(_, qi):
        return None, q_block(qi, qg[:, qi])

    _, blocks = jax.lax.scan(outer, None, jnp.arange(nq))
    # blocks: (nq, B, qc, KV, rep, D)
    return blocks.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, D)


def _merged_head_plan(n_heads: int, kv_heads: int, ctx: Ctx) -> int | None:
    """Decide whether to use merged-head TP attention; return pad count.

    Use it only where the grouped form is pathological AND padding is
    cheap: heads not divisible by the TP axis (GSPMD otherwise falls
    back to involuntary full rematerialization — measured 87-164 s
    collective terms on deepseek/llava, whose 56 heads pad to 64 for
    +14% attention FLOPs) while archs whose KV heads already shard
    cleanly (olmoe 16, granite 16) or whose pad would be >25% (qwen
    40 -> 80) measurably regress with it and keep the grouped form
    (§Perf It-2b/2c and the v3->v4 cell comparison in perf_log.md).
    Multi-pod meshes always keep the grouped form (repeat-backward
    resharding pathology, §Perf It-2c).
    """
    if (ctx.mesh is None or "model" not in ctx.mesh.axis_names
            or "pod" in ctx.mesh.axis_names):
        return None
    tp = ctx.mesh.devices.shape[ctx.mesh.axis_names.index("model")]
    if n_heads % tp == 0 or kv_heads % tp == 0:
        return None          # grouped form shards fine already
    target = n_heads
    while target % tp or (kv_heads and (target % kv_heads)):
        target += 1
    if target > n_heads * 1.25:
        return None          # padding too expensive (e.g. 40 -> 80)
    return target - n_heads


def attention(p: Params, x: jax.Array, cfg: ModelConfig, ctx: Ctx, *,
              positions: jax.Array, causal: bool = True,
              kv_override: tuple | None = None,
              lengths: jax.Array | None = None) -> jax.Array:
    """Full-sequence attention (train / prefill / encoder / cross).

    ``lengths``: optional (B,) valid lengths for ragged (serving)
    batches — forwarded to the masked attention path."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q, k, v = _qkv(p, x, cfg, ctx)
    if kv_override is not None:          # cross-attention: use encoder k/v
        k, v = kv_override
        q = rope(q, positions, cfg.rope_theta)
    else:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    n_pad = _merged_head_plan(cfg.n_heads, k.shape[2], ctx)
    if n_pad is not None:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, n_pad), (0, 0)))
    o = _gqa_full(q, k, v, causal=causal,
                  impl=ops.resolve_impl(ctx.plan.backend),
                  ctx=ctx if n_pad is not None else None,
                  config=ctx.plan, lengths=lengths, mesh=ctx.mesh)
    if n_pad:
        o = o[:, :, :cfg.n_heads]
    return linear(p["wo"], o.reshape(B, S, cfg.n_heads * hd), ctx,
                  split="row")


def attention_decode(p: Params, x: jax.Array, cfg: ModelConfig, ctx: Ctx, *,
                     cache: Params, pos: jax.Array) -> tuple[jax.Array, Params]:
    """One-token decode against a KV cache.

    x: (B, 1, d); cache: {"k": (B, S_max, KV, D), "v": ..., } ; pos: (B,)
    or scalar — the index the new token is written at.
    """
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    q, k, v = _qkv(p, x, cfg, ctx)
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))
    q = rope(q, pos_b[:, None], cfg.rope_theta)
    k = rope(k, pos_b[:, None], cfg.rope_theta)
    ck = _scatter_at(cache["k"], k, pos)
    cv = _scatter_at(cache["v"], v, pos)
    KV = ck.shape[2]
    rep = cfg.n_heads // KV
    qg = q.reshape(B, 1, KV, rep, hd)
    # Score dot stays in the cache dtype: requesting an f32 result makes
    # XLA upcast the operand — and the (loop-invariant) stacked cache
    # upcast gets hoisted out of the decode scan, materializing an f32
    # copy of the whole KV cache (+15 GiB/dev at 32k decode).  Only the
    # tiny (B,KV,rep,1,S) logits are upcast for the softmax.  On TPU the
    # MXU accumulates in f32 in hardware regardless.
    scores = jnp.einsum("bskrd,btkd->bkrst", qg, ck)
    logits = scores.astype(jnp.float32) * (hd ** -0.5)
    t_idx = jnp.arange(ck.shape[1])
    mask = t_idx[None, :] <= pos_b[:, None]            # (B, S_max)
    logits = jnp.where(mask[:, None, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkrst,btkd->bskrd", probs.astype(cv.dtype), cv)
    o = o.reshape(B, 1, cfg.n_heads * hd)
    return linear(p["wo"], o, ctx, split="row"), {"k": ck, "v": cv}


def attention_decode_paged(p: Params, x: jax.Array, cfg: ModelConfig,
                           ctx: Ctx, *, cache: Params,
                           page_table: jax.Array, pos: jax.Array
                           ) -> tuple[jax.Array, Params]:
    """One-token decode against a *paged* KV pool.

    x: (B, 1, d); cache: {"k": (P, ps, KV, D), "v": ...} — the shared
    page pool (P physical pages of ps tokens); page_table: (B, T)
    int32 logical->physical page map; pos: (B,) or scalar write index.

    The new token's K/V are scattered into the page holding position
    ``pos`` (slots past their allocation clip into the trash page their
    table points at).  The jnp path then gathers the table back into a
    contiguous (B, T*ps, KV, D) view and reuses :func:`attention_decode`'s
    exact masked-einsum math — same shapes, same reduction order, so a
    paged engine is *bitwise* equal to the unpaged one on this backend
    (garbage positions mask to exact -1e30 in both).  The
    pallas/interpret path instead runs :func:`repro.kernels.ops.paged_attention`,
    whose BlockSpec page gather never materializes the contiguous copy.
    """
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    q, k, v = _qkv(p, x, cfg, ctx)
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))
    q = rope(q, pos_b[:, None], cfg.rope_theta)
    k = rope(k, pos_b[:, None], cfg.rope_theta)
    ck = _scatter_paged(cache["k"], k, page_table, pos_b)
    cv = _scatter_paged(cache["v"], v, page_table, pos_b)
    KV = ck.shape[2]
    rep = cfg.n_heads // KV
    impl = ops.resolve_impl(ctx.plan.backend)
    if impl in ("pallas", "interpret"):
        o = ops.paged_attention(
            q.reshape(B, cfg.n_heads, hd), ck, cv, page_table,
            kv_lens=pos_b + 1, config=_attn_config(ctx.plan, impl),
            scale=hd ** -0.5)
        o = o.reshape(B, 1, cfg.n_heads * hd)
        return linear(p["wo"], o, ctx, split="row"), {"k": ck, "v": cv}
    ps = ck.shape[1]
    T = page_table.shape[1]
    kg = ck[page_table].reshape(B, T * ps, KV, hd)
    vg = cv[page_table].reshape(B, T * ps, KV, hd)
    qg = q.reshape(B, 1, KV, rep, hd)
    # identical math to attention_decode (see the dtype note there)
    scores = jnp.einsum("bskrd,btkd->bkrst", qg, kg)
    logits = scores.astype(jnp.float32) * (hd ** -0.5)
    t_idx = jnp.arange(T * ps)
    mask = t_idx[None, :] <= pos_b[:, None]
    logits = jnp.where(mask[:, None, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkrst,btkd->bskrd", probs.astype(vg.dtype), vg)
    o = o.reshape(B, 1, cfg.n_heads * hd)
    return linear(p["wo"], o, ctx, split="row"), {"k": ck, "v": cv}


def attention_decode_quantized(p: Params, x: jax.Array, cfg: ModelConfig,
                               ctx: Ctx, *, cache: Params, pos: jax.Array
                               ) -> tuple[jax.Array, Params]:
    """One-token decode against an int8-quantized KV cache.

    cache: {"k","v": int8 (B,S,KV,D), "k_scale","v_scale": (B,S,KV,1)}.
    New K/V are quantized with per-(position, kv-head) absmax scales;
    scores use the dequantized-in-register form (int8 reads from HBM —
    half the decode memory term of bf16; the dequant multiply fuses
    into the dot on TPU).
    """
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    q, k, v = _qkv(p, x, cfg, ctx)
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))
    q = rope(q, pos_b[:, None], cfg.rope_theta)
    k = rope(k, pos_b[:, None], cfg.rope_theta)

    def quant(t):
        scale = (jnp.max(jnp.abs(t), axis=-1, keepdims=True)
                 .astype(jnp.float32) / 127.0 + 1e-8)
        qt = jnp.clip(jnp.round(t.astype(jnp.float32) / scale),
                      -127, 127).astype(jnp.int8)
        return qt, scale.astype(ctx.dtype)

    qk, ks = quant(k)
    qv, vs = quant(v)
    ck = _scatter_at(cache["k"], qk, pos)
    cks = _scatter_at(cache["k_scale"], ks, pos)
    cv = _scatter_at(cache["v"], qv, pos)
    cvs = _scatter_at(cache["v_scale"], vs, pos)

    KV = ck.shape[2]
    rep = cfg.n_heads // KV
    qg = q.reshape(B, 1, KV, rep, hd)
    # int8 dot then per-position scale (exactly equal to dequant-first)
    raw = jnp.einsum("bskrd,btkd->bkrst", qg.astype(ctx.dtype),
                     ck.astype(ctx.dtype))
    scores = raw * cks[:, :, :, 0].transpose(0, 2, 1)[:, :, None, None, :]
    logits = scores.astype(jnp.float32) * (hd ** -0.5)
    t_idx = jnp.arange(ck.shape[1])
    mask = t_idx[None, :] <= pos_b[:, None]
    logits = jnp.where(mask[:, None, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    # fold v scales into the probabilities (per t position)
    pv = probs * cvs[:, :, :, 0].transpose(0, 2, 1)[
        :, :, None, None, :].astype(probs.dtype)
    o = jnp.einsum("bkrst,btkd->bskrd", pv.astype(ctx.dtype),
                   cv.astype(ctx.dtype))
    o = o.reshape(B, 1, cfg.n_heads * hd)
    out = linear(p["wo"], o, ctx, split="row")
    return out, {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}


def _scatter_paged(pool: jax.Array, new: jax.Array, page_table: jax.Array,
                   pos: jax.Array) -> jax.Array:
    """pool: (P, ps, KV, D); new: (B, 1, KV, D); write row b's token at
    sequence position ``pos[b]`` through its page table.

    The page index clips to the table length, so a slot decoding past
    its allocation lands on whatever its table's last entry points at —
    for retired/overflowing slots that is the trash page (id 0), whose
    contents are never read unmasked.  Duplicate trash writes across
    rows are fine for the same reason."""
    ps = pool.shape[1]
    T = page_table.shape[1]
    pos = pos.astype(jnp.int32)
    idx = jnp.clip(pos // ps, 0, T - 1)
    pids = jnp.take_along_axis(page_table, idx[:, None], axis=1)[:, 0]
    return pool.at[pids, pos % ps].set(new[:, 0].astype(pool.dtype))


def _scatter_at(c: jax.Array, new: jax.Array, pos: jax.Array) -> jax.Array:
    """c: (B, S, KV, D); new: (B, 1, KV, D); write new at position ``pos``.

    Scalar ``pos`` — all sequences decode at the same step (lock-step
    batches): a dynamic-update-slice, which XLA performs in place on
    the donated cache.  (B,) ``pos`` — per-sequence positions
    (continuous-batching slots): a vmapped per-row dynamic-update-slice,
    which lowers to a scatter XLA can still apply in place.  The old
    code collapsed every (B,) pos to ``pos[0]``, silently writing all
    rows at row 0's position — latent while serving was lock-step, live
    the moment slots decode at different depths.  A full-cache ``where``
    rewrite is avoided in both paths: it materializes a second
    cache-sized buffer per layer (measured +13 GiB/dev at 32k decode).
    """
    pos = jnp.asarray(pos)
    new = new.astype(c.dtype)
    zero = jnp.zeros((), jnp.int32)
    if pos.ndim == 0:
        return jax.lax.dynamic_update_slice(c, new, (zero, pos, zero, zero))
    return jax.vmap(
        lambda cb, nb, p: jax.lax.dynamic_update_slice(
            cb, nb, (p,) + (zero,) * (cb.ndim - 1))
    )(c, new, pos.astype(jnp.int32))


# ----------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / vanilla GELU)
# ----------------------------------------------------------------------
def init_mlp(key, cfg: ModelConfig, dtype=jnp.float32,
             d_ff: int | None = None) -> Params:
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_type in ("swiglu", "geglu"):
        return {
            "wi": init_linear(ks[0], cfg.d_model, d_ff, dtype=dtype),
            "wg": init_linear(ks[1], cfg.d_model, d_ff, dtype=dtype),
            "wo": init_linear(ks[2], d_ff, cfg.d_model, dtype=dtype),
        }
    return {
        "wi": init_linear(ks[0], cfg.d_model, d_ff, dtype=dtype),
        "wo": init_linear(ks[2], d_ff, cfg.d_model, dtype=dtype),
    }


def mlp(p: Params, x: jax.Array, cfg: ModelConfig, ctx: Ctx) -> jax.Array:
    h = linear(p["wi"], x, ctx, split="col")
    if cfg.mlp_type == "swiglu":
        h = jax.nn.silu(linear(p["wg"], x, ctx, split="col")) * h
    elif cfg.mlp_type == "geglu":
        h = jax.nn.gelu(linear(p["wg"], x, ctx, split="col")) * h
    else:
        h = jax.nn.gelu(h)
    return linear(p["wo"], h, ctx, split="row")


# ----------------------------------------------------------------------
# embeddings / lm head
# ----------------------------------------------------------------------
def init_embed(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    k1, k2 = jax.random.split(key)
    p = {"tokens": (jax.random.normal(k1, (cfg.vocab_size, cfg.d_model),
                                      jnp.float32) * 0.02).astype(dtype)}
    if not cfg.tie_embeddings:
        p["lm_head"] = _dense_init(k2, cfg.d_model, cfg.vocab_size, dtype)
    return p


def embed(p: Params, tokens: jax.Array, ctx: Ctx) -> jax.Array:
    return p["tokens"].astype(ctx.dtype)[tokens]


def unembed(p: Params, x: jax.Array, ctx: Ctx) -> jax.Array:
    """(B, S, d) -> (B, S, V) fp32 logits through the zero-stall engine.

    The LM head is the largest single GEMM of every family (d_model x
    vocab); it routes through ``ops.matmul`` like every other linear —
    the historical ``jnp.einsum`` here was exactly the silent-fallback
    class ``repro.analyze.lint_program`` exists to flag."""
    if "lm_head" in p:
        w = p["lm_head"].astype(ctx.dtype)
    else:
        w = p["tokens"].astype(ctx.dtype).T
    B, S, d = x.shape
    logits = _matmul(x.reshape(B * S, d), w, ctx, out_dtype=jnp.float32,
                     split="col")
    return logits.reshape(B, S, w.shape[-1])


def gather_last(x: jax.Array, lengths: jax.Array) -> jax.Array:
    """x: (B, S, d) -> (B, 1, d): per-row x[b, lengths[b] - 1].

    The ragged-prefill replacement for ``x[:, -1:]`` — each sequence's
    next-token position is its own last *valid* position."""
    idx = jnp.clip(lengths - 1, 0, x.shape[1] - 1).astype(jnp.int32)
    return jnp.take_along_axis(x, idx[:, None, None], axis=1)


def cross_entropy(logits: jax.Array, targets: jax.Array,
                  mask: jax.Array | None = None) -> jax.Array:
    """Mean token NLL; logits fp32 (B,S,V), targets (B,S) int.

    Label logits are extracted with a one-hot contraction instead of a
    gather: under GSPMD a gather along the vocab dim would replicate
    the (tokens x vocab) logits across the 'model' axis, while the
    one-hot einsum keeps V sharded (elementwise + reduce).
    """
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=logits.dtype)
    ll = jnp.einsum("bsv,bsv->bs", logits, onehot)
    nll = lse - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
