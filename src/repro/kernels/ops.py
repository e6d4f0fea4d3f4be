"""Public jit'd kernel wrappers with backend dispatch, padding, tuning.

Model code calls these entry points; they route to

  * the Pallas zero-stall kernels on TPU (backend "pallas"),
  * the same kernels under ``interpret=True`` for CPU validation
    (backend "interpret"),
  * identical-math jnp (backend "jnp") — used by the dry-run, whose
    XLA-CPU backend cannot lower Pallas-TPU kernels (DESIGN.md §3).

Backend "auto" picks pallas on TPU and jnp elsewhere, so the same
model code runs in tests, the dry-run and on real hardware.

Execution configuration — the single ``config`` argument
(:mod:`repro.plan`), resolved ahead of the kernel launch like the
paper's loop-nest CSR writes:

  * ``None``                 — the historical 128³ dobu default.
  * ``"auto"``               — resolve through :mod:`repro.tune`
    (analytic-model search, memoized in a persistent cache).
  * ``(bm, bn, bk)``         — explicit tile triple
    (``(bq, bkv)`` for :func:`attention`).
  * :class:`repro.plan.KernelConfig` — one complete validated
    configuration, including the backend.
  * :class:`repro.plan.Plan` — per-call-site lookup by bucketed
    ``OpKey``; misses follow the plan's default policy and are
    memoized, so a traced plan never touches the tuner at run time.

Results are bit-identical across configurations — the config only
changes the execution schedule, never the math.  Arbitrary shapes are
zero-padded up to tile multiples before the kernel and sliced back
after — padding contributes zeros to the contraction, so results are
exact.

The pre-plan keyword spelling (``impl=``, ``bm=/bn=/bk=``,
``variant=``, ``slots=``, ``grid_order=``, ``bq=/bkv=``, ``tiling=``)
still works behind a deprecation shim (one ``DeprecationWarning`` per
call) and produces bit-identical results to its ``config=``
equivalent.
"""

from __future__ import annotations

import contextlib
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as _obs
from repro import plan as _plan
from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.grouped_matmul import grouped_zero_stall_matmul
from repro.kernels.paged_attention import paged_attention as _paged
from repro.kernels.quantized_matmul import (
    quantized_grouped_zero_stall_matmul,
    quantized_zero_stall_matmul,
)
from repro.kernels.zero_stall_matmul import zero_stall_matmul
from repro.plan import UNSET as _UNSET, KernelConfig, Plan
from repro.quant.tensor import QTensor, quantize_rows

__all__ = ["matmul", "grouped_matmul", "attention", "paged_attention",
           "host_tiled_matmul", "quantized_matmul", "layer_weight_counts",
           "quantized_grouped_matmul", "resolve_impl",
           "reset_fallback_warnings", "fallback_counts", "FallbackError",
           "strict_fallbacks"]


_WEIGHT_PREFIX = "matmul.weight_"


def _record(op: str, *, M, N, K, dtype, backend, config=None, groups=1,
            batch_heads=1, in_place: bool | None = None) -> None:
    """Report this dispatch to the observability layer (when on).

    These wrappers execute at **trace time** under ``jax.jit``, so a
    record is one traced call site per (shape, dtype, backend, config)
    signature — exactly the kernel set of the compiled program, which
    is what the utilization table prices (see
    :mod:`repro.obs.kernel_watch`).  Off by default: one boolean check.

    ``in_place`` marks a matmul whose weight is one layer of a stack
    (``matmul(..., layer=)``): whether the kernel read it in place or
    it was sliced out first.  That is counted always, by call site and
    by the layer's weight bytes (:func:`layer_weight_counts`).
    """
    if in_place is not None:
        kind = _WEIGHT_PREFIX + ("in_place" if in_place else "sliced")
        _obs.counter_inc(kind + ".calls")
        _obs.counter_inc(kind + ".bytes",
                         int(K) * int(N) * np.dtype(dtype).itemsize)
    if _obs.enabled():
        _obs.record_dispatch(op, M=M, N=N, K=K, dtype=dtype,
                             backend=backend, config=config, groups=groups,
                             batch_heads=batch_heads)


def resolve_impl(impl: str) -> str:
    """Resolve the ``"auto"`` backend vocabulary to a concrete backend.

    "auto" means: the Pallas zero-stall kernels when a TPU backs the
    process, the identical-math jnp reference otherwise (tests and the
    dry-run); "pallas" / "interpret" / "jnp" pass through unchanged.
    """
    if impl != "auto":
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def _pad_to(x: jax.Array, mults: tuple[int, ...]) -> jax.Array:
    pads = []
    for dim, m in zip(x.shape, mults):
        pads.append((0, (-dim) % m if m else 0))
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


def _dtype_from_name(name: str):
    try:
        return np.dtype(name)
    except TypeError:
        return getattr(jnp, name)


def _config_out_dtype(config, key: "_plan.OpKey | None" = None):
    """A config's ``out_dtype`` without schedule resolution.

    Priority: the Plan entry for ``key`` (a pure lookup — no tuning,
    no memoization), then the KernelConfig / plan-default field.  The
    jnp backend short-circuits before ``plan.resolve`` runs and the
    quantized wrappers default their dtype early, but the contract is
    one priority order — explicit argument > per-entry > plan default
    — identical on every backend."""
    candidates = []
    if isinstance(config, KernelConfig):
        candidates.append(config)
    elif isinstance(config, Plan):
        if key is not None:
            hit = config.lookup(key)
            if hit is not None:
                candidates.append(hit)
        if isinstance(config.default, KernelConfig):
            candidates.append(config.default)
    for cfg in candidates:
        if cfg.out_dtype is not None:
            return _dtype_from_name(cfg.out_dtype)
    return None


def _legacy_config(op: str, config, legacy: dict):
    """The single adapter folding the deprecated per-call kwargs into
    the ``config`` vocabulary (emits one DeprecationWarning)."""
    used = {k: v for k, v in legacy.items() if v is not _UNSET}
    if not used:
        return config
    warnings.warn(
        f"ops.{op}: the {sorted(used)} keyword(s) are deprecated; pass the "
        f"single config= argument instead (a repro.plan.KernelConfig, a "
        f"Plan, 'auto', a tile tuple or None)",
        DeprecationWarning, stacklevel=3)
    if config is not None:
        raise TypeError(
            f"ops.{op}: cannot mix config= with the deprecated "
            f"{sorted(used)} keyword(s)")
    impl = used.pop("impl", "auto")
    tiling = used.pop("tiling", None)
    if tiling == "auto":
        # historical behavior: "auto" overrode any explicit tile/variant
        # keywords — preserved bit-for-bit by the shim
        return Plan(backend=impl)
    if tiling is not None:
        if op == "attention":
            if not (isinstance(tiling, (tuple, list)) and len(tiling) == 2):
                raise ValueError(f"attention tiling must be None, 'auto' or "
                                 f"(bq, bkv), got {tiling!r}")
            used["bq"], used["bkv"] = (int(t) for t in tiling)
        else:
            if not (isinstance(tiling, (tuple, list)) and len(tiling) == 3):
                raise ValueError(f"tiling must be None, 'auto' or a "
                                 f"(bm, bn, bk) triple, got {tiling!r}")
            used["bm"], used["bn"], used["bk"] = (int(t) for t in tiling)
    return KernelConfig(backend=impl, **used)


def matmul(a: jax.Array, b: jax.Array, *, layer=None, config=None,
           out_dtype=None, impl=_UNSET, bm=_UNSET, bn=_UNSET, bk=_UNSET,
           variant=_UNSET, slots=_UNSET, grid_order=_UNSET,
           tiling=_UNSET) -> jax.Array:
    """C = A @ B through the zero-stall engine.

    The workhorse entry point: every linear layer in the model zoo
    routes here (``models.layers.linear``).  ``config`` selects the
    backend and the execution configuration (see the module docstring
    for the vocabulary); the trailing keywords are the deprecated
    pre-plan spelling.  Arbitrary shapes are zero-padded to tile
    multiples and sliced back — padding contributes zeros to the
    contraction, so results are exact and independent of the config.

    With ``layer`` (a scalar, traced or not), ``b`` is a stacked
    (L, K, N) weight and the product is ``a @ b[layer]``.  Where the
    kernel runs and its tiles divide K and N, the stack is viewed as
    (L*K, N) — a bitcast — and the kernel DMAs the layer's tiles
    straight out of it at row ``layer * K``: no copy of the layer is
    made.  Otherwise (the jnp backend, or tiles that would pad the
    weight) ``b[layer]`` is sliced out and takes the ordinary path.
    Both give the same bits: either reads ``layer`` as
    ``lax.dynamic_index_in_dim`` does (a negative index counts from
    the end, then it is clamped into the stack).
    """
    config = _legacy_config("matmul", config, {
        "impl": impl, "bm": bm, "bn": bn, "bk": bk, "variant": variant,
        "slots": slots, "grid_order": grid_order, "tiling": tiling})
    backend = resolve_impl(_plan.config_backend(config, "matmul"))
    M, N, K = a.shape[0], b.shape[-1], a.shape[1]
    if out_dtype is None:
        out_dtype = _config_out_dtype(config, _plan.OpKey(
            "matmul", M, N, K, dtype=_plan.dtype_name(a.dtype)))
    if backend == "jnp":
        if layer is not None:
            b = jax.lax.dynamic_index_in_dim(b, layer, keepdims=False)
        _record("matmul", M=M, N=N, K=K, dtype=a.dtype, backend=backend,
                in_place=None if layer is None else False)
        return _ref.matmul_ref(a, b, out_dtype)
    cfg = _plan.resolve(config, op="matmul", M=M, N=N, K=K,
                        dtype=a.dtype, backend=backend)
    in_place = None
    row0 = 0
    if layer is not None:
        in_place = K % cfg.bk == 0 and N % cfg.bn == 0
        if in_place:
            row0 = _stack_index(layer, b.shape[0]) * K
            b = b.reshape(-1, N)
        else:
            b = jax.lax.dynamic_index_in_dim(b, layer, keepdims=False)
    _record("matmul", M=M, N=N, K=K, dtype=a.dtype, backend=backend,
            config=cfg, in_place=in_place)
    ap = _pad_to(a, (cfg.bm, cfg.bk))
    bp = _pad_to(b, (cfg.bk, cfg.bn))
    c = zero_stall_matmul(ap, bp, row0,
                          interpret=(backend == "interpret"),
                          out_dtype=out_dtype, **cfg.matmul_kwargs())
    return c[:M, :N]


def _stack_index(layer, L: int):
    """``layer`` as ``lax.dynamic_index_in_dim`` reads it from an
    L-deep stack: a negative index counts from the end, then the index
    is clamped into 0..L-1."""
    if isinstance(layer, int):
        return min(max(layer + L if layer < 0 else layer, 0), L - 1)
    layer = jnp.asarray(layer, jnp.int32)
    return jnp.clip(jnp.where(layer < 0, layer + L, layer), 0, L - 1)


def layer_weight_counts() -> dict[str, int]:
    """How the stacked layer weights reached the matmul kernel since
    ``obs.reset_counters("matmul.weight_")``: ``{"in_place.calls",
    "in_place.bytes", "sliced.calls", "sliced.bytes"}``, each a traced
    call site (once per compiled program) and the bytes of the one
    layer's weight it reads."""
    pre = _WEIGHT_PREFIX
    got = {k[len(pre):]: v for k, v in _obs.counters(pre).items()}
    return {k: got.get(k, 0) for k in ("in_place.calls", "in_place.bytes",
                                       "sliced.calls", "sliced.bytes")}


def grouped_matmul(a: jax.Array, b: jax.Array, *, config=None,
                   out_dtype=None, impl=_UNSET, bm=_UNSET, bn=_UNSET,
                   bk=_UNSET, variant=_UNSET, slots=_UNSET,
                   tiling=_UNSET) -> jax.Array:
    """(G,M,K) @ (G,K,N) -> (G,M,N) per-expert matmul.

    The MoE dispatch path (``models.moe.moe_mlp``): expert FFNs run as
    one grouped kernel whose revolving buffer streams across expert
    boundaries, so the MXU never idles on an expert switch.  Same
    ``config`` vocabulary as :func:`matmul`.
    """
    config = _legacy_config("grouped_matmul", config, {
        "impl": impl, "bm": bm, "bn": bn, "bk": bk, "variant": variant,
        "slots": slots, "tiling": tiling})
    backend = resolve_impl(_plan.config_backend(config, "grouped_matmul"))
    G, M, K = a.shape
    N = b.shape[2]
    if out_dtype is None:
        out_dtype = _config_out_dtype(config, _plan.OpKey(
            "grouped_matmul", M, N, K, groups=G,
            dtype=_plan.dtype_name(a.dtype)))
    if backend == "jnp":
        _record("grouped_matmul", M=M, N=N, K=K, dtype=a.dtype,
                backend=backend, groups=G)
        return _ref.grouped_matmul_ref(a, b, out_dtype)
    cfg = _plan.resolve(config, op="grouped_matmul", M=M, N=N,
                        K=K, dtype=a.dtype, backend=backend,
                        groups=G)
    _record("grouped_matmul", M=M, N=N, K=K, dtype=a.dtype,
            backend=backend, config=cfg, groups=G)
    ap = _pad_to(a, (1, cfg.bm, cfg.bk))
    bp = _pad_to(b, (1, cfg.bk, cfg.bn))
    c = grouped_zero_stall_matmul(ap, bp, bm=cfg.bm, bn=cfg.bn, bk=cfg.bk,
                                  variant=cfg.variant, slots=cfg.slots,
                                  interpret=(backend == "interpret"),
                                  out_dtype=out_dtype)
    return c[:, :M, :N]


def quantized_matmul(x: jax.Array, qw: QTensor, *, config=None,
                     out_dtype=None, impl=_UNSET, bm=_UNSET, bn=_UNSET,
                     bk=_UNSET, variant=_UNSET, slots=_UNSET,
                     grid_order=_UNSET, tiling=_UNSET) -> jax.Array:
    """C = x @ qw through the int8 zero-stall engine (W8A8).

    ``x`` (M, K) is a full-precision activation, dynamically quantized
    per row (:func:`repro.quant.quantize_rows` — padding rows are
    exact zeros, so the path stays lengths-aware); ``qw`` is a
    :class:`~repro.quant.QTensor` weight.  The int8 kernel accumulates
    in exact int32 and fuses the ``row_scale * col_scale`` dequant
    into its epilogue.  Auto configs tune in the *int8* configuration
    space — 1-byte tiles halve the VMEM footprint, so the legal tile
    space is a superset of bf16's (and plan entries key on the int8
    dtype, never colliding with bf16 entries).

    ``fmt="fp8"`` QTensors take the simulated-fp8 route: dequantize to
    the activation dtype and run the standard (still Pallas) kernel —
    the e4m3 storage rounding is the simulation.
    """
    config = _legacy_config("quantized_matmul", config, {
        "impl": impl, "bm": bm, "bn": bn, "bk": bk, "variant": variant,
        "slots": slots, "grid_order": grid_order, "tiling": tiling})
    if not isinstance(qw, QTensor):
        raise TypeError(f"qw must be a QTensor, got {type(qw).__name__}")
    if qw.fmt != "int8":
        return matmul(x, qw.dequantize(x.dtype), config=config,
                      out_dtype=out_dtype)
    backend = resolve_impl(_plan.config_backend(config, "matmul"))
    M, N, K = x.shape[0], qw.shape[1], x.shape[1]
    out_dtype = (out_dtype
                 or _config_out_dtype(config, _plan.OpKey(
                     "matmul", M, N, K, dtype="int8"))
                 or x.dtype)
    x_q, x_s = quantize_rows(x)
    w_q, w_s = qw.data, qw.scale.astype(jnp.float32)
    if backend == "jnp":
        _record("matmul", M=M, N=N, K=K, dtype="int8", backend=backend)
        return _ref.quantized_matmul_ref(x_q, w_q, x_s, w_s, out_dtype)
    cfg = _plan.resolve(config, op="matmul", M=M, N=N, K=K,
                        dtype=jnp.int8, backend=backend)
    _record("matmul", M=M, N=N, K=K, dtype="int8", backend=backend,
            config=cfg)
    c = quantized_zero_stall_matmul(
        _pad_to(x_q, (cfg.bm, cfg.bk)), _pad_to(w_q, (cfg.bk, cfg.bn)),
        _pad_to(x_s, (cfg.bm, 1)), _pad_to(w_s, (1, cfg.bn)),
        interpret=(backend == "interpret"), out_dtype=out_dtype,
        **cfg.matmul_kwargs())
    return c[:M, :N]


def quantized_grouped_matmul(x: jax.Array, qw: QTensor, *, config=None,
                             out_dtype=None, impl=_UNSET, bm=_UNSET,
                             bn=_UNSET, bk=_UNSET, variant=_UNSET,
                             slots=_UNSET, tiling=_UNSET) -> jax.Array:
    """(G,M,K) activations @ QTensor (G,K,N) expert bank (W8A8 MoE)."""
    config = _legacy_config("quantized_grouped_matmul", config, {
        "impl": impl, "bm": bm, "bn": bn, "bk": bk, "variant": variant,
        "slots": slots, "tiling": tiling})
    if not isinstance(qw, QTensor):
        raise TypeError(f"qw must be a QTensor, got {type(qw).__name__}")
    if qw.fmt != "int8":
        return grouped_matmul(x, qw.dequantize(x.dtype), config=config,
                              out_dtype=out_dtype)
    backend = resolve_impl(_plan.config_backend(config, "grouped_matmul"))
    G, M, K = x.shape
    N = qw.shape[2]
    out_dtype = (out_dtype
                 or _config_out_dtype(config, _plan.OpKey(
                     "grouped_matmul", M, N, K, groups=G, dtype="int8"))
                 or x.dtype)
    x_q, x_s = quantize_rows(x)
    w_q, w_s = qw.data, qw.scale.astype(jnp.float32)
    if backend == "jnp":
        _record("grouped_matmul", M=M, N=N, K=K, dtype="int8",
                backend=backend, groups=G)
        return _ref.quantized_grouped_matmul_ref(x_q, w_q, x_s, w_s,
                                                 out_dtype)
    cfg = _plan.resolve(config, op="grouped_matmul", M=M, N=N,
                        K=K, dtype=jnp.int8, backend=backend,
                        groups=G)
    _record("grouped_matmul", M=M, N=N, K=K, dtype="int8",
            backend=backend, config=cfg, groups=G)
    c = quantized_grouped_zero_stall_matmul(
        _pad_to(x_q, (1, cfg.bm, cfg.bk)), _pad_to(w_q, (1, cfg.bk, cfg.bn)),
        _pad_to(x_s, (1, cfg.bm, 1)), _pad_to(w_s, (1, 1, cfg.bn)),
        bm=cfg.bm, bn=cfg.bn, bk=cfg.bk, variant=cfg.variant,
        slots=cfg.slots, interpret=(backend == "interpret"),
        out_dtype=out_dtype)
    return c[:, :M, :N]


_FALLBACK_WARNED: set[str] = set()
_FALLBACK_PREFIX = "ops.fallback."
_STRICT_FALLBACKS = False
_STRICT_ALLOW: tuple[str, ...] = ()


class FallbackError(RuntimeError):
    """An ops.* entry point would leave the zero-stall Pallas path.

    Raised instead of the warn-once RuntimeWarning when strict mode is
    on (``strict_fallbacks()`` / ``attention(..., strict=True)``), so
    parity tests and production plans can *prove* no call site routes
    to the jnp reference silently."""


@contextlib.contextmanager
def strict_fallbacks(enable: bool = True, *,
                     allow: tuple[str, ...] = ()):
    """Treat any kernel fallback as an error inside this context.

    ``allow`` lists fallback keys (see ``fallback_counts``) that stay
    on warn-once behavior — the explicit allowlist for fallbacks that
    are understood and accepted (they are still counted).
    """
    global _STRICT_FALLBACKS, _STRICT_ALLOW
    prev = (_STRICT_FALLBACKS, _STRICT_ALLOW)
    _STRICT_FALLBACKS, _STRICT_ALLOW = bool(enable), tuple(allow)
    try:
        yield
    finally:
        _STRICT_FALLBACKS, _STRICT_ALLOW = prev


def reset_fallback_warnings() -> None:
    """Forget which fallback reasons have already warned AND zero the
    fallback counters.

    ``_warn_fallback_once`` is process-global warn-once state; tests
    asserting on the warning / the counters (or their absence) call
    this (via an autouse fixture) so their outcome is
    order-independent.
    """
    _FALLBACK_WARNED.clear()
    _obs.reset_counters(_FALLBACK_PREFIX)


def fallback_counts() -> dict[str, int]:
    """{fallback key -> times taken} since the last reset.

    The queryable face of ``_warn_fallback_once``: the warning fires
    once per key, but every occurrence increments an always-on
    :mod:`repro.obs` counter, so production runs and tests can assert
    ``ops.fallback_counts() == {}`` instead of scraping warnings.
    Counts are per *trace* (these wrappers run at jit-trace time), i.e.
    the number of compiled programs that baked in a fallback.
    """
    pre = _FALLBACK_PREFIX
    return {k[len(pre):]: v for k, v in _obs.counters(pre).items()}


def _warn_fallback_once(key: str, reason: str,
                        strict: bool | None = None) -> None:
    """The Pallas path is the product; a silent jnp fallback is a perf
    cliff (serving batches are exactly the ragged shapes that used to
    take it).  Any fallback still taken is announced once per key and
    counted every time (``fallback_counts``); under strict mode
    (per-call ``strict=True`` or a ``strict_fallbacks()`` context) it
    raises :class:`FallbackError` unless the key is allowlisted."""
    _obs.counter_inc(_FALLBACK_PREFIX + key)
    if strict is None:
        strict = _STRICT_FALLBACKS
    if strict and key not in _STRICT_ALLOW:
        raise FallbackError(
            f"ops fallback {key!r}: {reason}; the zero-stall Pallas path "
            f"is NOT used (strict mode — allowlist the key via "
            f"strict_fallbacks(allow=...) if this is intentional)")
    if key not in _FALLBACK_WARNED:
        _FALLBACK_WARNED.add(key)
        warnings.warn(f"ops.attention: falling back to the jnp reference "
                      f"({reason}); the zero-stall Pallas path is NOT used",
                      RuntimeWarning, stacklevel=3)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *, config=None,
              causal: bool = True, scale: float | None = None,
              q_lens: jax.Array | None = None,
              kv_lens: jax.Array | None = None,
              q_offsets: jax.Array | None = None,
              strict: bool | None = None,
              impl=_UNSET, bq=_UNSET, bkv=_UNSET,
              tiling=_UNSET) -> jax.Array:
    """(B,H,S,D) flash attention; ref oracle for jnp path.

    ``config`` follows the module vocabulary (tile tuples are
    ``(bq, bkv)`` pairs here; a KernelConfig contributes its
    ``bq``/``bkv`` fields).  ``q_lens``/``kv_lens``: optional (B,)
    per-sequence valid lengths (variable-length/continuous batches).
    ``q_offsets``: optional (B,) absolute position of query row 0 —
    chunked prefill, where a chunk of rows attends to the full kv
    stripe with a shifted causal frontier.  Non-tile-multiple sequence
    lengths are zero-padded up to the tile and masked via the length
    operands — padding contributes exact zeros, so ragged serving
    shapes stay on the Pallas kernel instead of silently routing to the
    reference path.  ``strict=True`` turns any remaining fallback into
    a :class:`FallbackError` (default: the ambient
    ``strict_fallbacks()`` mode).
    """
    config = _legacy_config("attention", config, {
        "impl": impl, "bq": bq, "bkv": bkv, "tiling": tiling})
    backend = resolve_impl(_plan.config_backend(config, "attention"))
    B, H, Sq, D = q.shape
    Skv = k.shape[2]
    if backend == "jnp":
        _record("attention", M=Sq, N=D, K=Skv, dtype=q.dtype,
                backend=backend, batch_heads=B * H)
        return _ref.flash_attention_ref(q, k, v, causal=causal, scale=scale,
                                        q_lens=q_lens, kv_lens=kv_lens,
                                        q_offsets=q_offsets)
    if (causal and Sq != Skv and q_lens is None and kv_lens is None
            and q_offsets is None):
        # kernel causal is start-aligned (row i == position i); the
        # historical ref is end-aligned for Sq != Skv — don't guess.
        _warn_fallback_once("attention_causal_unaligned",
                            "causal attention with Sq != Skv and no "
                            "length operands has ambiguous alignment",
                            strict=strict)
        _record("attention", M=Sq, N=D, K=Skv, dtype=q.dtype,
                backend="jnp", batch_heads=B * H)
        return _ref.flash_attention_ref(q, k, v, causal=causal, scale=scale)
    cfg = _plan.resolve(config, op="attention", M=Sq, N=D, K=Skv,
                        dtype=q.dtype, backend=backend, batch_heads=B * H)
    _record("attention", M=Sq, N=D, K=Skv, dtype=q.dtype, backend=backend,
            config=cfg, batch_heads=B * H)
    bq_ = min(cfg.bq, Sq)
    bkv_ = min(cfg.bkv, Skv)
    if Sq % bq_ or Skv % bkv_:
        # pad to tile multiples and mask — the lengths default to the
        # unpadded extents (absolute, so offsets shift them), so
        # padding contributes exact zeros.
        if q_lens is None:
            q_lens = jnp.full((B,), Sq, jnp.int32)
            if q_offsets is not None:
                q_lens = q_lens + q_offsets
        if kv_lens is None:
            kv_lens = jnp.full((B,), Skv, jnp.int32)
        q = _pad_to(q, (1, 1, bq_, 1))
        k = _pad_to(k, (1, 1, bkv_, 1))
        v = _pad_to(v, (1, 1, bkv_, 1))
    out = _flash(q, k, v, q_lens=q_lens, kv_lens=kv_lens,
                 q_offsets=q_offsets, bq=bq_, bkv=bkv_, causal=causal,
                 scale=scale, interpret=(backend == "interpret"))
    return out[:, :, :Sq]


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    page_table: jax.Array, *, kv_lens: jax.Array,
                    config=None, scale: float | None = None) -> jax.Array:
    """Decode attention over a paged KV pool (see
    :mod:`repro.kernels.paged_attention`).

    ``q`` (B, H, D) is the batch's last-position queries; ``k_pool`` /
    ``v_pool`` (P, ps, KV, D) the shared page pool; ``page_table``
    (B, T) maps each slot's logical pages to physical ones;
    ``kv_lens`` (B,) the valid kv extents.  ``config`` only selects
    the backend — the page geometry *is* the schedule (block = one
    page), so there is no tile resolution step and, by construction,
    no fallback: every backend runs the same table-gather math, which
    is what keeps this entry trivially clean under
    :func:`strict_fallbacks`.
    """
    backend = resolve_impl(_plan.config_backend(config, "attention"))
    B, H, D = q.shape
    ps, KV = k_pool.shape[1], k_pool.shape[2]
    T = page_table.shape[1]
    _record("attention", M=H // KV, N=D, K=T * ps, dtype=q.dtype,
            backend=backend, batch_heads=B * KV)
    if backend == "jnp":
        return _ref.paged_attention_ref(q, k_pool, v_pool, page_table,
                                        kv_lens=kv_lens, scale=scale)
    return _paged(q, k_pool, v_pool, page_table, kv_lens=kv_lens,
                  scale=scale, interpret=(backend == "interpret"))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def host_tiled_matmul(a: jax.Array, b: jax.Array, *,
                      bm: int = 128, bn: int = 128, bk: int = 128
                      ) -> jax.Array:
    """Pre-ZONL baseline: software-managed tile loop.

    The tile loop nest runs as `lax.fori_loop` bookkeeping (index
    arithmetic, bounds tests, dynamic slices) instead of the grid
    sequencer — the analogue of Snitch's 2-instructions-per-outer-
    iteration overhead.  Used by benchmarks to quantify the ZONL win;
    math is identical.  (Deliberately outside the plan/config API: this
    IS the old world the plan machinery replaces.)
    """
    (M, K), (_, N) = a.shape, b.shape
    if M % bm or N % bn or K % bk:
        raise ValueError(f"host_tiled_matmul: shape {(M, K, N)} not tiled "
                         f"by (bm, bn, bk)={(bm, bn, bk)}")
    gm, gn, gk = M // bm, N // bn, K // bk

    def body(t, c):
        i = t // (gn * gk)
        j = (t // gk) % gn
        k = t % gk
        a_t = jax.lax.dynamic_slice(a, (i * bm, k * bk), (bm, bk))
        b_t = jax.lax.dynamic_slice(b, (k * bk, j * bn), (bk, bn))
        prod = jnp.dot(a_t, b_t, preferred_element_type=jnp.float32)
        c_t = jax.lax.dynamic_slice(c, (i * bm, j * bn), (bm, bn))
        return jax.lax.dynamic_update_slice(c, c_t + prod, (i * bm, j * bn))

    c = jnp.zeros((M, N), jnp.float32)
    c = jax.lax.fori_loop(0, gm * gn * gk, body, c)
    return c.astype(a.dtype)
