"""Jaxpr program lint: prove a traced program stays on the fast path.

The serving/training programs are jitted closures; whether every
matmul actually routes through the zero-stall kernels — and whether
the fused K-step decode block really syncs with the host only at its
boundary — is visible in the jaxpr.  :func:`lint_program` walks the
jaxpr of a ``trace_model``-style abstract eval and flags:

* ``ZS-P001`` — a ``dot_general`` issued outside both a ``pallas_call``
  and the sanctioned ``repro.kernels`` dispatch layer (the silent-jnp
  class of bug PR 2 fixed by hand for attention).
* ``ZS-P002`` — host callbacks / infeed / outfeed baked into the
  program: a sync point inside the fused dispatch the block-decode
  design exists to eliminate.
* ``ZS-P003`` — on the quantized path, int8 weights dequantized into a
  full-precision ``dot_general`` (W8A8 defeated by an upcast).

Known-intentional sites (the SSD recurrence einsums, the O(1) decode
attention against the cache, the tiny MoE router, the loss) are
allowlisted by source location; the allowlist is an explicit,
reviewable constant.  Every lint records how often each allowlist
entry actually sanctioned a ``dot_general``
(``report.meta["allow_hits"]``); :func:`check_allowlist` turns
entries that matched nothing across a full-family sweep into
``ZS-P004`` warnings — a stale entry is a hole the next silent
fallback walks through unseen.
"""

from __future__ import annotations

import contextlib
import math
import os
from typing import Any, Callable

import jax
import jaxlib

from repro.analyze.diagnostics import Diagnostic, Report

__all__ = ["lint_program", "check_allowlist", "merge_allow_hits",
           "DEFAULT_ALLOW"]

#: Source-location substrings whose dot_generals are sanctioned.
#: `repro/kernels/` is the dispatch layer itself (its jnp reference
#: paths are deliberate, counted fallbacks, not silent ones); the
#: model-side entries are the paper-intentional non-GEMM contractions.
DEFAULT_ALLOW = (
    "repro/kernels/",            # ops.* dispatch + its jnp references
    "repro/models/ssm.py",       # SSD chunked recurrence (bandwidth-bound)
    "in attention_decode",       # O(1) per-token attention vs the cache
    "in _gqa_full",              # backend-dispatched attention: routes to
                                 # ops.attention on pallas/interpret; its
                                 # einsums ARE the explicit jnp backend
    "repro/models/moe.py",       # router logits (tokens x n_experts, tiny)
    "in cross_entropy",          # loss: one-hot contraction, not a GEMM
)

_CALLBACK_PRIMS = ("infeed", "outfeed", "debug_print")
# frames in jax, jaxlib and the contextlib wrappers around jax entry
# points are never the user call site
_SKIP = (*(os.path.dirname(m.__file__) + os.sep for m in (jax, jaxlib)),
         contextlib.__file__)
_INT_DTYPES = ("int8", "uint8", "int4", "uint4")


def _jaxpr_of(target) -> Any:
    """Accept a Jaxpr / ClosedJaxpr, or anything with a ``.jaxpr``."""
    while hasattr(target, "jaxpr"):
        target = target.jaxpr
    return target


def _source_of(eqn) -> str:
    """``file:line in function`` of the innermost frame outside jax.

    Reads the public traceback an equation carries
    (``eqn.source_info.traceback``); its frames run innermost first, so
    the first one outside jax, jaxlib and contextlib is the user call
    site."""
    tb = eqn.source_info.traceback
    for frame in (tb.frames if tb is not None else ()):
        if not frame.file_name.startswith(_SKIP):
            return (f"{frame.file_name}:{frame.line_num} in "
                    f"{frame.function_name}")
    return "<unknown>"


def _dot_flops(eqn) -> float:
    """FLOPs of one dot_general from its operand avals."""
    try:
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval.shape
        rhs = eqn.invars[1].aval.shape
        batch = math.prod(lhs[d] for d in lb) if lb else 1
        contract = math.prod(lhs[d] for d in lc) if lc else 1
        lfree = math.prod(s for d, s in enumerate(lhs)
                          if d not in set(lc) | set(lb))
        rfree = math.prod(s for d, s in enumerate(rhs)
                          if d not in set(rc) | set(rb))
        return 2.0 * batch * contract * lfree * rfree
    except Exception:
        return float("inf")     # un-analyzable: never silently below cut


def _sub_jaxprs(eqn):
    """Child jaxprs of one equation (cond branches, scan body, pjit...)
    — excluding pallas_call, whose param jaxpr is the kernel *body*
    (running on the MXU is the point, not a fallback)."""
    if eqn.primitive.name == "pallas_call":
        return
    for val in eqn.params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if hasattr(v, "jaxpr") or hasattr(v, "eqns"):
                yield _jaxpr_of(v)


def _is_float(aval) -> bool:
    try:
        return jax.numpy.issubdtype(aval.dtype, jax.numpy.floating)
    except Exception:
        return False


def _walk(jaxpr, diags: list[Diagnostic], *, allow: tuple[str, ...],
          min_flops: float, quant: bool,
          hits: dict[str, int] | None = None) -> None:
    # taint: vars holding values dequantized from int8-class storage
    # (convert_element_type int->float), propagated through the
    # elementwise/layout glue a dequant typically runs through
    tainted: set[Any] = set()
    glue = {"mul", "add", "sub", "broadcast_in_dim", "transpose",
            "reshape", "convert_element_type", "squeeze", "slice"}
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        src = _source_of(eqn)
        matched = [a for a in allow if a in src]
        allowed = bool(matched)
        if hits is not None and name == "dot_general":
            for a in matched:
                hits[a] += 1

        if "callback" in name or name in _CALLBACK_PRIMS:
            diags.append(Diagnostic(
                rule="ZS-P002", severity="error", where=src,
                message=f"host sync point baked into the program: "
                        f"primitive {name!r}",
                hint="hoist host interaction out of the jitted block — "
                     "the fused K-step dispatch must sync only at its "
                     "boundary"))

        if name == "convert_element_type":
            in_aval = eqn.invars[0].aval
            if (str(getattr(in_aval, "dtype", "")) in _INT_DTYPES
                    and _is_float(eqn.outvars[0].aval)):
                tainted.add(eqn.outvars[0])
        elif name in glue:
            if any(v in tainted for v in eqn.invars
                   if not isinstance(v, jax.extend.core.Literal)):
                tainted.update(eqn.outvars)

        if name == "dot_general":
            flops = _dot_flops(eqn)
            if not allowed and flops >= min_flops:
                diags.append(Diagnostic(
                    rule="ZS-P001", severity="error", where=src,
                    message=f"matmul ({flops:.0f} flops) issued outside "
                            f"the zero-stall kernels (top-level "
                            f"dot_general)",
                    hint="route it through repro.kernels.ops (matmul / "
                         "grouped_matmul / attention), or allowlist the "
                         "site in repro.analyze.program_lint"))
            if quant and flops >= min_flops and any(
                    v in tainted for v in eqn.invars
                    if not isinstance(v, jax.extend.core.Literal)):
                diags.append(Diagnostic(
                    rule="ZS-P003", severity="warning", where=src,
                    message="int8 weights are dequantized into a "
                            "full-precision matmul on the quantized path",
                    hint="route through ops.quantized_matmul (W8A8, "
                         "int32 accumulate) instead of dequantizing "
                         "ahead of the kernel"))

        for sub in _sub_jaxprs(eqn):
            _walk(sub, diags, allow=allow, min_flops=min_flops,
                  quant=quant, hits=hits)


def lint_program(target: Callable | Any, *args,
                 allow: tuple[str, ...] = DEFAULT_ALLOW,
                 min_flops: float = 0.0, quant: bool = False,
                 **kwargs) -> Report:
    """Lint a program for fallback matmuls, host syncs, fp32 upcasts.

    ``target`` is either an already-traced ``Jaxpr``/``ClosedJaxpr``
    or a callable, which is traced with ``jax.make_jaxpr`` over
    ``*args``/``**kwargs`` (abstract values — ``ShapeDtypeStruct``
    pytrees — work; no FLOPs run).  ``allow`` is the sanctioned-site
    list (substring match against ``file:line in function``);
    ``min_flops`` ignores glue contractions below the cut; ``quant``
    additionally arms the dequant-upcast rule (``ZS-P003``).
    """
    if callable(target) and not hasattr(target, "eqns") \
            and not hasattr(target, "jaxpr"):
        target = jax.make_jaxpr(target)(*args, **kwargs)
    jaxpr = _jaxpr_of(target)
    diags: list[Diagnostic] = []
    hits = {a: 0 for a in allow}
    _walk(jaxpr, diags, allow=tuple(allow), min_flops=float(min_flops),
          quant=quant, hits=hits)
    report = Report(diags)
    report.meta["allow_hits"] = hits
    return report


def merge_allow_hits(*hit_maps: dict) -> dict:
    """Sum per-entry allowlist hit counts across several lints."""
    out: dict[str, int] = {}
    for hm in hit_maps:
        for entry, n in (hm or {}).items():
            out[entry] = out.get(entry, 0) + int(n)
    return out


def check_allowlist(hits: dict, *, allow: tuple[str, ...] = DEFAULT_ALLOW,
                    where: str = "program-lint") -> Report:
    """Flag allowlist entries that sanctioned nothing (``ZS-P004``).

    ``hits`` is a (merged) ``allow_hits`` map from :func:`lint_program`
    runs.  Only meaningful over a sweep that exercises every model
    family — a single-arch run legitimately leaves other families'
    entries unmatched, so the driver arms this check only for
    full-family sweeps.  Stale entries are warnings: they don't break
    the build, but each one is a sanctioned site that no longer exists,
    silently widening what a future fallback may hide behind.
    """
    report = Report()
    for entry in allow:
        if hits.get(entry, 0) == 0:
            report.add(Diagnostic(
                rule="ZS-P004", severity="warning", where=where,
                message=f"allowlist entry {entry!r} matched no "
                        f"dot_general site across the sweep (stale)",
                hint="remove the entry from DEFAULT_ALLOW, or restore "
                     "the sanctioned site it used to cover"))
    report.meta["allow_hits"] = dict(hits)
    return report
