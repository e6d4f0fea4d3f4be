"""Kernel-IR verification: prove the emitted Pallas kernels implement
the zero-stall schedule the config layer models.

The other analyzer layers reason about *configs* (``check_config``,
``simulate_schedule``) and *programs* (``lint_program``) — both trust
that the kernels in :mod:`repro.kernels` actually realize the N-slot
revolving-buffer schedule.  This layer closes that gap: it traces an
``ops.*`` entry point with ``jax.make_jaxpr``, digs the ``pallas_call``
equations out of the jaxpr, and verifies the IR itself.

Two verification modes, selected by the kernel's declared
:class:`~repro.kernels.meta.ScheduleContract`:

* **managed DMA** (the matmul families): the kernel body is replayed
  concretely for every grid step — scalar index arithmetic,
  ``program_id``, ``cond`` branches and ``pjit`` sub-jaxprs are
  evaluated to concrete integers, and every ``dma_start`` /
  ``dma_wait`` / slot ``get`` is recorded as an event.  The observed
  slot-residency timeline (prologue, per-step compute slot, prefetch
  look-ahead) is then diffed against
  :meth:`repro.core.pipeline.RevolvingSchedule.timeline` and the Dobu
  bank mapping (:func:`repro.analyze.hazards.bank_access_pattern`).

* **pipeline-managed** (the attention families): operand movement is
  the Pallas pipeline's automatic double buffering, so the BlockSpec
  index maps are evaluated symbolically over the full grid instead
  (scalar-prefetch operands supplied as concrete arrays).

Rules (catalog in ``analyze.RULES`` / docs/ARCHITECTURE.md):

* ``ZS-K001`` — kernel/config schedule divergence: the IR-derived
  residency timeline does not match the declared contract or the
  ``RevolvingSchedule``/``simulate_schedule``/bank model.
* ``ZS-K002`` — overlapping VMEM windows across in-flight grid steps:
  a DMA lands in a slot the same step computes from, or overwrites a
  primed-but-unconsumed window (WAR on the real IR).
* ``ZS-K003`` — bank conflict in the derived access pattern under the
  double-buffering-aware Dobu interconnect.
* ``ZS-K004`` — grid order revisits an output block after eviction
  (the accumulation run is split — broken HBM streaming).
* ``ZS-K005`` — ``input_output_aliases`` overlap a live input window
  (an aliased output write lands on a block a later step still reads).
* ``ZS-K006`` — a manual DMA reads a window outside its source operand
  (a row offset into a stacked weight runs past the stack).

Small operands the body reads as values (the matmul's row offset into
B) are taken from the traced program where they are constants
(``KernelIR.operand_values``); a DMA whose source depends on any other
value escapes the interpreter (``ZS-K001``).
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Any, Callable, Iterable

import numpy as np

from repro.analyze.diagnostics import Diagnostic, Report
from repro.analyze.hazards import bank_access_pattern, simulate_schedule
from repro.core.pipeline import RevolvingSchedule
from repro.kernels.meta import ScheduleContract, contract_for

__all__ = ["KernelIR", "find_pallas_eqns", "extract_kernel_ir",
           "trace_kernel_irs", "lint_kernel_ir", "lint_kernels",
           "KERNEL_FAMILIES"]

#: sweep families understood by :func:`lint_kernels`
KERNEL_FAMILIES = ("zero_stall", "grouped", "quantized", "attention")

#: full-grid index-map sweeps are capped here (diagnosed, not silent)
_GRID_SWEEP_CAP = 4096


# ----------------------------------------------------------------------
# IR extraction
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class BlockInfo:
    """One operand's BlockSpec as recovered from the IR."""

    index: int                 # position among the pallas_call operands
    kind: str                  # "in" | "out"
    blocked: bool              # False = ANY memory space (manual DMA)
    block_shape: tuple
    array_shape: tuple
    index_map: Any             # ClosedJaxpr grid indices -> block indices


@dataclasses.dataclass
class KernelIR:
    """Everything the verifier needs from one ``pallas_call``."""

    name: str
    grid: tuple
    blocks: list
    jaxpr: Any                 # kernel body jaxpr
    consts: tuple
    num_inputs: int
    num_outputs: int
    num_index_operands: int
    num_scratch_operands: int
    input_output_aliases: tuple
    dimension_semantics: tuple | None
    contract: ScheduleContract | None
    operand_values: dict = dataclasses.field(default_factory=dict)

    @property
    def total_steps(self) -> int:
        return int(math.prod(self.grid)) if self.grid else 1

    def body_ref_region(self, index: int) -> str:
        """Classify a body invar: scalar / input / output / scratch."""
        n_idx = self.num_index_operands
        n_in = n_idx + self.num_inputs
        n_out = n_in + self.num_outputs
        if index < n_idx:
            return "scalar"
        if index < n_in:
            return "input"
        if index < n_out:
            return "output"
        return "scratch"


def find_pallas_eqns(jaxpr) -> list:
    """All ``pallas_call`` equations in ``jaxpr``, recursively
    (entry points wrap the kernel call in ``pjit``/``custom_jvp``
    layers — the search descends through every sub-jaxpr param)."""
    return [eqn for eqn, _ in _pallas_calls(jaxpr)]


#: shape/dtype-only primitives a constant operand is folded through
_FOLD_PRIMS = ("convert_element_type", "reshape", "broadcast_in_dim",
               "squeeze")
#: largest operand (elements) whose constant value is kept
_FOLD_CAP = 64
#: primitives whose sub-jaxpr takes the equation's operands as invars
_CALL_PRIMS = ("pjit", "jit", "closed_call", "core_call",
               "custom_jvp_call", "custom_vjp_call")


def _pallas_calls(jaxpr) -> list:
    """``[(eqn, {operand position: value})]`` for every ``pallas_call``
    in ``jaxpr``: the value of each small operand that is a constant of
    the traced program (a literal folded through reshapes, casts and
    broadcasts, across ``pjit`` boundaries)."""
    import jax

    found = []
    seen = set()

    def walk(jx, known):
        if id(jx) in seen:
            return
        seen.add(id(jx))

        def value(atom):
            if isinstance(atom, jax.extend.core.Literal):
                return np.asarray(atom.val)
            return known.get(atom)

        for eqn in jx.eqns:
            ins = [value(v) for v in eqn.invars]
            name = eqn.primitive.name
            if name == "pallas_call":
                found.append((eqn, {i: v for i, v in enumerate(ins)
                                    if v is not None}))
            elif name in _FOLD_PRIMS and ins and ins[0] is not None:
                out = eqn.outvars[0].aval
                if math.prod(out.shape) <= _FOLD_CAP:
                    v = ins[0].astype(out.dtype)
                    known[eqn.outvars[0]] = (
                        np.full(out.shape, v.item(), out.dtype)
                        if v.size == 1 else v.reshape(out.shape))
            for val in eqn.params.values():
                vals = val if isinstance(val, (tuple, list)) else (val,)
                for v in vals:
                    sub = getattr(v, "jaxpr", None)
                    sub = sub if sub is not None and hasattr(
                        sub, "eqns") else (v if hasattr(v, "eqns")
                                           else None)
                    if sub is None:
                        continue
                    # a call passes its operands straight to its invars
                    passed = ({iv: x for iv, x in zip(sub.invars, ins)
                               if x is not None}
                              if name in _CALL_PRIMS
                              and len(sub.invars) == len(ins) else {})
                    walk(sub, passed)

    walk(_strip_closed(jaxpr), {})
    return found


def _strip_closed(jx):
    while hasattr(jx, "jaxpr"):
        jx = jx.jaxpr
    return jx


def extract_kernel_ir(eqn, operand_values=None) -> KernelIR:
    """Read one ``pallas_call`` equation into a :class:`KernelIR`;
    ``operand_values`` maps operand positions to known values."""
    gm = eqn.params["grid_mapping"]
    blocks = []
    for i, bmap in enumerate(gm.block_mappings):
        aval_s = str(bmap.transformed_block_aval)
        blocks.append(BlockInfo(
            index=i,
            kind="in" if i < gm.num_inputs else "out",
            blocked="<any>" not in aval_s.lower(),
            block_shape=tuple(getattr(d, "block_size", d)
                              for d in bmap.block_shape),
            array_shape=tuple(bmap.array_aval.shape),
            index_map=bmap.index_map_jaxpr))
    body = eqn.params["jaxpr"]
    consts = tuple(getattr(body, "consts", ()))
    body = _strip_closed(body)
    name = eqn.params["name"]
    mosaic = (eqn.params.get("compiler_params") or {}).get("mosaic_tpu")
    sem = getattr(mosaic, "dimension_semantics", None)
    return KernelIR(
        name=name,
        grid=tuple(gm.grid),
        blocks=blocks,
        jaxpr=body,
        consts=consts,
        num_inputs=gm.num_inputs,
        num_outputs=gm.num_outputs,
        num_index_operands=gm.num_index_operands,
        num_scratch_operands=gm.num_scratch_operands,
        input_output_aliases=tuple(eqn.params.get(
            "input_output_aliases") or ()),
        dimension_semantics=tuple(sem) if sem is not None else None,
        contract=contract_for(name),
        operand_values=dict(operand_values or {}))


def trace_kernel_irs(fn: Callable, *args, **kwargs) -> list:
    """``jax.make_jaxpr`` an entry point and extract every kernel IR
    (with the values of its constant operands)."""
    import jax

    jx = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    return [extract_kernel_ir(e, vals) for e, vals in _pallas_calls(jx)]


# ----------------------------------------------------------------------
# concrete jaxpr interpretation
# ----------------------------------------------------------------------
class _Uninterpretable(Exception):
    """The kernel body escaped the concrete scalar interpreter."""


class _Opaque:
    """Placeholder for array values the verifier does not track."""

    __slots__ = ()

    def __repr__(self):
        return "<opaque>"


_OPAQUE = _Opaque()


@dataclasses.dataclass
class _RefVal:
    """A Ref flowing through the interpreter; ``array`` holds the
    concrete value for scalar-prefetch operands (readable via get)."""

    index: int
    array: Any = None


def _is_scalar(v) -> bool:
    return isinstance(v, (bool, int, float, np.bool_, np.integer,
                          np.floating))


def _trunc_div(a, b):
    q = abs(a) // abs(b)
    return q if (a >= 0) == (b >= 0) else -q


_SCALAR_PRIMS: dict = {
    "add": lambda a, b: a + b,
    "sub": lambda a, b: a - b,
    "mul": lambda a, b: a * b,
    "div": _trunc_div,                       # lax.div: C-style for ints
    "rem": lambda a, b: a - b * _trunc_div(a, b),
    "max": max,
    "min": min,
    "neg": lambda a: -a,
    "abs": abs,
    "sign": lambda a: (a > 0) - (a < 0),
    "floor": math.floor,
    "ceil": math.ceil,
    "eq": lambda a, b: a == b,
    "ne": lambda a, b: a != b,
    "lt": lambda a, b: a < b,
    "le": lambda a, b: a <= b,
    "gt": lambda a, b: a > b,
    "ge": lambda a, b: a >= b,
    "and": lambda a, b: (a and b) if isinstance(a, (bool, np.bool_))
    else a & b,
    "or": lambda a, b: (a or b) if isinstance(a, (bool, np.bool_))
    else a | b,
    "xor": lambda a, b: bool(a) != bool(b)
    if isinstance(a, (bool, np.bool_)) else a ^ b,
    "not": lambda a: not a,
    "stop_gradient": lambda a: a,
}


class _Interp:
    """Concrete evaluator for kernel bodies and BlockSpec index maps.

    Scalar arithmetic on values derived from ``program_id`` is computed
    exactly; everything tensor-valued degrades to :data:`_OPAQUE`.  A
    per-call ``on_event`` hook observes the stateful primitives
    (``dma_start``/``dma_wait``/``get``/``swap``/``dot_general``) — the
    raw material of the residency timeline.
    """

    def __init__(self, program_ids=(), grid=(), on_event=None):
        self.program_ids = tuple(program_ids)
        self.grid = tuple(grid)
        self.on_event = on_event

    # -- helpers -------------------------------------------------------
    def _lit(self, val):
        arr = np.asarray(val)
        if arr.ndim == 0:
            return arr.item()
        return _OPAQUE

    def run(self, jaxpr, consts, args) -> list:
        import jax

        env: dict = {}

        def read(atom):
            if isinstance(atom, jax.extend.core.Literal):
                return self._lit(atom.val)
            return env[atom]

        for cv, c in zip(jaxpr.constvars, consts):
            env[cv] = self._lit(c) if np.ndim(c) == 0 else _OPAQUE
        if len(args) != len(jaxpr.invars):
            raise _Uninterpretable(
                f"arity mismatch: {len(args)} args for "
                f"{len(jaxpr.invars)} invars")
        for iv, a in zip(jaxpr.invars, args):
            env[iv] = a
        for eqn in jaxpr.eqns:
            invals = [read(x) for x in eqn.invars]
            outs = self._eqn(eqn, invals)
            for ov, o in zip(eqn.outvars, outs):
                if type(ov).__name__ != "DropVar":
                    env[ov] = o
        return [read(x) for x in jaxpr.outvars]

    # -- one equation --------------------------------------------------
    def _eqn(self, eqn, invals) -> list:
        prim = eqn.primitive.name
        n_out = len(eqn.outvars)

        if prim == "program_id":
            axis = eqn.params["axis"]
            if axis >= len(self.program_ids):
                raise _Uninterpretable(f"program_id axis {axis} out of "
                                       f"range")
            return [self.program_ids[axis]]

        if prim == "num_programs":
            axis = eqn.params["axis"]
            if axis >= len(self.grid):
                raise _Uninterpretable(f"num_programs axis {axis} out "
                                       f"of range")
            return [self.grid[axis]]

        if prim in ("jit", "closed_call", "core_call", "custom_jvp_call",
                    "custom_vjp_call"):
            sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            if sub is None:
                return [_OPAQUE] * n_out
            consts = tuple(getattr(sub, "consts", ()))
            return self.run(_strip_closed(sub), consts, invals)

        if prim == "cond":
            pred = invals[0]
            if not _is_scalar(pred):
                raise _Uninterpretable("cond predicate is not concrete")
            branches = eqn.params["branches"]
            idx = min(max(int(pred), 0), len(branches) - 1)
            br = branches[idx]
            return self.run(_strip_closed(br),
                            tuple(getattr(br, "consts", ())), invals[1:])

        if prim in ("while", "scan"):
            raise _Uninterpretable(f"{prim} inside kernel body")

        if prim in ("dma_start", "dma_wait", "get", "swap",
                    "dot_general"):
            if self.on_event is None and prim == "get":
                return [self._get(eqn, invals)]
            if self.on_event is not None:
                out = self.on_event(prim, eqn, invals)
                if out is not None:
                    return out if isinstance(out, list) else [out]
            if prim == "get":
                return [self._get(eqn, invals)]
            return [_OPAQUE] * n_out

        if prim == "multiple_of":
            # an alignment promise to the compiler: hold the kernel to it
            v = invals[0]
            if _is_scalar(v) and any(v % m for m in eqn.params["values"]):
                raise _Uninterpretable(
                    f"index {v} breaks its multiple_of"
                    f"{tuple(eqn.params['values'])} hint")
            return [v]

        if prim == "convert_element_type":
            v = invals[0]
            if isinstance(v, (bool, np.bool_)):
                return [int(v)]
            return [v]

        if prim == "select_n":
            pred = invals[0]
            if _is_scalar(pred):
                cases = invals[1:]
                return [cases[min(max(int(pred), 0), len(cases) - 1)]]
            return [_OPAQUE]

        if prim == "integer_pow":
            v = invals[0]
            if _is_scalar(v):
                return [v ** eqn.params["y"]]
            return [_OPAQUE]

        if prim in ("broadcast_in_dim", "reshape", "squeeze"):
            v = invals[0]
            shape = eqn.params.get("shape", eqn.params.get(
                "new_sizes", ()))
            if _is_scalar(v) and tuple(shape or ()) == ():
                return [v]
            return [_OPAQUE] * n_out

        fn = _SCALAR_PRIMS.get(prim)
        if fn is not None and all(_is_scalar(v) for v in invals):
            return [fn(*invals)]
        return [_OPAQUE] * n_out

    # -- get on a concrete scalar-prefetch ref -------------------------
    def _get(self, eqn, invals):
        ref = invals[0]
        if not isinstance(ref, _RefVal) or ref.array is None:
            return _OPAQUE
        idx = invals[1:]
        arr = np.asarray(ref.array)
        if not idx:
            return _OPAQUE if arr.ndim else arr.item()
        if arr.ndim == 1 and len(idx) == 1 and _is_scalar(idx[0]):
            return arr[int(idx[0])].item()
        return _OPAQUE


# ----------------------------------------------------------------------
# index-map evaluation
# ----------------------------------------------------------------------
def _eval_index_map(ir: KernelIR, block: BlockInfo, ids,
                    scalar_args) -> tuple:
    cj = block.index_map
    jx = _strip_closed(cj)
    n_extra = len(jx.invars) - len(ids)
    extras = [_RefVal(-1, arr) for arr in scalar_args[:max(n_extra, 0)]]
    if n_extra > len(extras):
        extras += [_RefVal(-1, None)] * (n_extra - len(extras))
    out = _Interp().run(jx, tuple(getattr(cj, "consts", ())),
                        list(ids) + extras)
    vals = []
    for v in out:
        if not _is_scalar(v):
            raise _Uninterpretable(
                f"index map of operand {block.index} did not reduce to "
                f"integers at grid point {tuple(ids)}")
        vals.append(int(v))
    return tuple(vals)


def _grid_points(ir: KernelIR, cap: int):
    return itertools.islice(
        itertools.product(*(range(g) for g in ir.grid)), cap)


# ----------------------------------------------------------------------
# managed-DMA body replay
# ----------------------------------------------------------------------
@dataclasses.dataclass
class _Start:
    """One observed ``dma_start`` into a slot buffer."""

    step: int
    ref: int                   # destination body-invar index
    slot: int
    src: int                   # source body-invar index
    src_key: tuple             # concrete source start indices
    pre: bool                  # issued before this step's first read
    consumer: int | None = None


@dataclasses.dataclass
class _StepTrace:
    step: int
    starts: list = dataclasses.field(default_factory=list)
    reads: list = dataclasses.field(default_factory=list)  # (ref, slot)
    waits: list = dataclasses.field(default_factory=list)  # (ref, slot)


def _split_dma(invals) -> list:
    """Partition dma invals into (ref, [concrete scalars...]) groups."""
    groups = []
    for v in invals:
        if isinstance(v, _RefVal):
            groups.append((v, []))
        elif groups:
            groups[-1][1].append(v)
    return groups


def _replay_body(ir: KernelIR, steps: int, on_dma=None) -> list:
    """Interpret the body once per grid step, recording DMA/read
    events.  Returns a list of :class:`_StepTrace`.  The operands in
    ``ir.operand_values`` are readable refs;
    ``on_dma(step, src_ref, src_starts, dst_ref, dst_starts)`` sees
    every ``dma_start``."""
    values = ir.operand_values
    traces = []
    for t, ids in enumerate(_grid_points(ir, steps)):
        tr = _StepTrace(step=t)
        seen_read = [False]

        def on_event(prim, eqn, invals, tr=tr, t=t, seen_read=seen_read):
            if prim in ("dma_start", "dma_wait"):
                groups = _split_dma(invals)
                if len(groups) < 2:
                    raise _Uninterpretable(f"{prim} with "
                                           f"{len(groups)} ref groups")
                (src, src_idx), (dst, dst_idx) = groups[0], groups[1]
                if any(not _is_scalar(v) for v in src_idx + dst_idx):
                    raise _Uninterpretable(
                        f"{prim} index not concrete at step {t}")
                slot = int(dst_idx[0]) if dst_idx else 0
                if prim == "dma_start":
                    if on_dma is not None:
                        on_dma(t, src, [int(v) for v in src_idx], dst,
                               [int(v) for v in dst_idx])
                    tr.starts.append(_Start(
                        step=t, ref=dst.index, slot=slot, src=src.index,
                        src_key=tuple(int(v) for v in src_idx),
                        pre=not seen_read[0]))
                else:
                    tr.waits.append((dst.index, slot))
                return []
            if prim == "get":
                ref = invals[0]
                if (isinstance(ref, _RefVal)
                        and ir.body_ref_region(ref.index) == "scratch"
                        and len(invals) > 1 and _is_scalar(invals[1])):
                    seen_read[0] = True
                    tr.reads.append((ref.index, int(invals[1])))
                    return [_OPAQUE]
                return None          # fall through to concrete get
            if prim == "dot_general":
                seen_read[0] = True
            return None

        interp = _Interp(program_ids=ids, grid=ir.grid,
                         on_event=on_event)
        args = [_RefVal(i, values.get(i))
                for i in range(len(ir.jaxpr.invars))]
        interp.run(ir.jaxpr, ir.consts, args)
        traces.append(tr)
    return traces


def _resolve_consumers(traces: list) -> None:
    """Mark each start with the step whose compute read its content."""
    live: dict = {}
    for tr in traces:
        # within a step, source order is: pre-starts, reads, post-starts
        for st in (s for s in tr.starts if s.pre):
            live[(st.ref, st.slot)] = st
        for ref, slot in tr.reads:
            st = live.get((ref, slot))
            if st is not None and st.consumer is None:
                st.consumer = tr.step
        for st in (s for s in tr.starts if not s.pre):
            live[(st.ref, st.slot)] = st


def _slot_depth(ir: KernelIR, traces: list):
    """Slot-buffer depth from the DMA destination refs' leading dim."""
    refs = {st.ref for tr in traces for st in tr.starts}
    depths = set()
    for r in refs:
        shape = tuple(ir.jaxpr.invars[r].aval.shape)
        depths.add(shape[0] if shape else 1)
    return refs, depths


def _analyze_managed(ir: KernelIR, report: Report, where: str,
                     max_steps: int) -> None:
    total = ir.total_steps
    steps = min(total, max_steps)
    outside = []

    def on_dma(t, src, starts, dst, dst_starts):
        # the window is the destination ref less its indexed dims
        shape = tuple(ir.jaxpr.invars[src.index].aval.shape)
        window = tuple(ir.jaxpr.invars[dst.index].aval.shape
                       )[len(dst_starts):]
        if len(starts) == len(shape) == len(window) and any(
                s < 0 or s + w > n
                for s, w, n in zip(starts, window, shape)):
            outside.append((t, src.index, tuple(starts), window, shape))

    try:
        traces = _replay_body(ir, steps, on_dma)
    except _Uninterpretable as e:
        report.add(Diagnostic(
            rule="ZS-K001", severity="error", where=where,
            message=f"kernel body escaped the IR interpreter: {e}",
            hint="keep slot/DMA indexing a pure function of "
                 "program_id and of operands whose values are "
                 "constants of the traced program"))
        return
    if outside:
        t, src, starts, window, shape = outside[0]
        report.add(Diagnostic(
            rule="ZS-K006", severity="error", where=where,
            message=f"step {t} DMAs the {window} window at {starts} "
                    f"of operand {src}, outside its {shape} extent "
                    f"({len(outside)} such DMA(s))",
            hint="a row offset into a stacked operand must keep every "
                 "tile inside the stack"))
    _resolve_consumers(traces)

    dst_refs, depths = _slot_depth(ir, traces)
    if not dst_refs:
        report.add(Diagnostic(
            rule="ZS-K001", severity="error", where=where,
            message="managed-DMA contract but no slot DMA observed",
            hint="kernel should stream operands via make_async_copy"))
        return
    if len(depths) != 1:
        report.add(Diagnostic(
            rule="ZS-K001", severity="error", where=where,
            message=f"slot buffers disagree on depth: {sorted(depths)}"))
        return
    slots = depths.pop()
    declared = ir.contract.slots if ir.contract else None
    if declared is not None and declared != slots:
        report.add(Diagnostic(
            rule="ZS-K001", severity="error", where=where,
            message=f"kernel name declares {declared} slot(s) but the "
                    f"scratch buffers hold {slots}"))

    # --- ZS-K002: WAR / in-flight overlap on the real slot windows ---
    hazards = 0
    unconsumed: dict = {}
    for tr in traces:
        pre_slots = {(s.ref, s.slot) for s in tr.starts if s.pre}
        read_slots = set(tr.reads)
        inflight = pre_slots & read_slots if tr.step > 0 else set()
        if tr.step == 0 and slots == 1:
            # the single prologue fill is waited before the read
            inflight = set()
        for _ref, slot in sorted(inflight):
            hazards += 1
            report.add(Diagnostic(
                rule="ZS-K002", severity="error", where=where,
                message=f"step {tr.step} computes from slot {slot} "
                        f"while a DMA is in flight into the same slot "
                        f"(WAR overlap across in-flight grid steps)",
                hint="prefetch must target the slot drained one step "
                     "earlier, never the live compute slot"))
        for st in tr.starts:
            key = (st.ref, st.slot)
            prev = unconsumed.get(key)
            if (prev is not None and prev.consumer is None
                    and (st.step, st.pre) != (0, True)):
                hazards += 1
                report.add(Diagnostic(
                    rule="ZS-K002", severity="error", where=where,
                    message=f"step {st.step} DMA overwrites slot "
                            f"{st.slot} still holding the unconsumed "
                            f"window primed at step {prev.step}",
                    hint="increase slot depth or delay the prefetch"))
            unconsumed[key] = st

    # --- ZS-K003: derived bank pattern under the Dobu interconnect ---
    model = bank_access_pattern(slots, total)
    for tr in traces:
        reads = {s for _, s in tr.reads}
        compute_banks = {b for s in reads for b in (2 * s, 2 * s + 1)}
        compute_banks |= {2 * slots}         # accumulator bank
        dma_banks = {b for st in tr.starts if st.pre and tr.step > 0
                     for b in (2 * st.slot, 2 * st.slot + 1)}
        if compute_banks & dma_banks:
            report.add(Diagnostic(
                rule="ZS-K003", severity="error", where=where,
                message=f"step {tr.step}: concurrent DMA and compute "
                        f"touch banks "
                        f"{sorted(compute_banks & dma_banks)} — the "
                        f"derived pattern conflicts under the Dobu "
                        f"mapping",
                hint="slot s maps to banks {2s, 2s+1}; producer and "
                     "consumer slots must differ"))
        elif slots > 1 and tr.step > 0 and tr.step < len(model):
            want_c, want_d = model[tr.step]
            have_d = dma_banks
            if reads and (compute_banks != set(want_c)
                          or (have_d and have_d != set(want_d))):
                report.add(Diagnostic(
                    rule="ZS-K001", severity="error", where=where,
                    message=f"step {tr.step}: derived bank pattern "
                            f"({sorted(compute_banks)} / "
                            f"{sorted(have_d)}) diverges from the Dobu "
                            f"model ({sorted(want_c)} / "
                            f"{sorted(want_d)})"))

    # --- ZS-K001: residency timeline vs RevolvingSchedule -------------
    _diff_timeline(ir, report, where, traces, slots, total, steps)

    # --- ZS-K001: cross-check the config-layer hazard simulation ------
    overlap_obs = any(st.pre for tr in traces if tr.step > 0
                      for st in tr.starts)
    sim_errors = [d for d in simulate_schedule(
        total, slots, overlap=overlap_obs, where=where)
        if d.severity == "error"]
    if bool(sim_errors) != bool(hazards):
        report.add(Diagnostic(
            rule="ZS-K001", severity="error", where=where,
            message=f"IR-derived schedule and simulate_schedule "
                    f"disagree: simulation "
                    f"{'finds' if sim_errors else 'finds no'} hazards, "
                    f"the replayed IR "
                    f"{'does' if hazards else 'does not'}"))


def _diff_timeline(ir, report, where, traces, slots, total,
                   steps) -> None:
    """Diff observed prologue/phases against the canonical schedule."""
    sched = RevolvingSchedule(steps=total, slots=slots)
    tl = sched.timeline()

    t0 = traces[0]
    prologue_obs = sorted({(st.consumer, st.slot)
                           for st in t0.starts if st.pre
                           if st.consumer is not None})
    want = sorted(set(tl["prologue"]))
    if prologue_obs != want:
        report.add(Diagnostic(
            rule="ZS-K001", severity="error", where=where,
            message=f"prologue primes {prologue_obs} (step, slot) but "
                    f"the schedule model expects {want}"))

    by_step = {ph[0]: ph for ph in tl["phases"]}
    for tr in traces:
        t = tr.step
        _, want_cs, want_ps, want_pslot = by_step[t]
        read_slots = {s for _, s in tr.reads}
        if read_slots and read_slots != {want_cs}:
            report.add(Diagnostic(
                rule="ZS-K001", severity="error", where=where,
                message=f"step {t} computes from slot(s) "
                        f"{sorted(read_slots)}; the schedule model "
                        f"assigns slot {want_cs}"))
        # steady-state prefetches: pre-compute for slots>1, the
        # serialized post-compute copy for slots==1
        pref = [st for st in tr.starts
                if (st.pre and t > 0) or (not st.pre)]
        if want_ps is None:
            if pref:
                report.add(Diagnostic(
                    rule="ZS-K001", severity="error", where=where,
                    message=f"step {t} issues a prefetch; the schedule "
                            f"model expects none here"))
            continue
        if not pref:
            if want_ps < steps:
                report.add(Diagnostic(
                    rule="ZS-K001", severity="error", where=where,
                    message=f"step {t} issues no prefetch; the "
                            f"schedule model expects step {want_ps} "
                            f"into slot {want_pslot}"))
            continue
        bad_slot = {st.slot for st in pref} - {want_pslot}
        if bad_slot:
            report.add(Diagnostic(
                rule="ZS-K001", severity="error", where=where,
                message=f"step {t} prefetches into slot(s) "
                        f"{sorted(bad_slot)}; the schedule model "
                        f"expects slot {want_pslot}"))
        consumers = {st.consumer for st in pref
                     if st.consumer is not None}
        if consumers and (consumers != {want_ps}
                          and want_ps < steps):
            report.add(Diagnostic(
                rule="ZS-K001", severity="error", where=where,
                message=f"step {t}'s prefetch is consumed at step(s) "
                        f"{sorted(consumers)}; the schedule model "
                        f"expects look-ahead to step {want_ps}"))
        if slots > 1 and any(not st.pre for st in pref):
            report.add(Diagnostic(
                rule="ZS-K001", severity="error", where=where,
                message=f"step {t} issues its prefetch after compute "
                        f"(serialized); an overlap schedule with "
                        f"{slots} slots must prefetch concurrently"))


# ----------------------------------------------------------------------
# index-map / grid checks (all families)
# ----------------------------------------------------------------------
def _check_contract_shape(ir: KernelIR, report: Report,
                          where: str) -> None:
    c = ir.contract
    if c is None:
        return
    if len(ir.grid) != c.grid_rank:
        report.add(Diagnostic(
            rule="ZS-K001", severity="error", where=where,
            message=f"grid rank {len(ir.grid)} != declared "
                    f"{c.grid_rank}"))
    sem = ir.dimension_semantics
    if sem is None:
        return
    need_seq = (range(len(sem)) if c.sequential_axes == "all"
                else [len(sem) - 1])
    for ax in need_seq:
        if sem[ax] != "arbitrary":
            report.add(Diagnostic(
                rule="ZS-K001", severity="error", where=where,
                message=f"grid axis {ax} is {sem[ax]!r} but the "
                        f"schedule carries state across it — it must "
                        f"be sequential ('arbitrary')",
                hint="parallel semantics let Mosaic reorder steps, "
                     "breaking DMA/accumulator carry"))


def _check_output_streaming(ir: KernelIR, report: Report, where: str,
                            scalar_args) -> None:
    """ZS-K004: each output block must be one contiguous run over the
    grid walk — a revisit after eviction splits the accumulation and
    re-fetches a window already streamed back to HBM."""
    outs = [b for b in ir.blocks if b.kind == "out" and b.blocked]
    if not outs:
        return
    for block in outs:
        seen: dict = {}
        current = None
        try:
            for t, ids in enumerate(_grid_points(ir, _GRID_SWEEP_CAP)):
                blk = _eval_index_map(ir, block, ids, scalar_args)
                if blk == current:
                    continue
                if blk in seen:
                    report.add(Diagnostic(
                        rule="ZS-K004", severity="error", where=where,
                        message=f"grid step {t} revisits output block "
                                f"{blk} of operand {block.index} "
                                f"(first run ended at step "
                                f"{seen[blk]}) — the accumulation run "
                                f"is split and the evicted window "
                                f"re-fetched",
                        hint="keep the contraction axis innermost in "
                             "the grid walk"))
                    break
                if current is not None:
                    seen[current] = t - 1
                current = blk
        except _Uninterpretable as e:
            report.add(Diagnostic(
                rule="ZS-K004", severity="error", where=where,
                message=f"output index map not statically evaluable: "
                        f"{e}"))


def _window_range(blk: tuple, shape: tuple) -> tuple:
    """Block indices -> per-dim (start, stop) element ranges."""
    return tuple((i * d, i * d + d) for i, d in zip(blk, shape))


def _ranges_overlap(ra, rb) -> bool:
    return all(a0 < b1 and b0 < a1 for (a0, a1), (b0, b1) in zip(ra, rb))


def _check_aliases(ir: KernelIR, report: Report, where: str,
                   scalar_args) -> None:
    """ZS-K005: an aliased output write must never land on a window a
    later grid step still reads."""
    if not ir.input_output_aliases:
        return
    by_index = {b.index: b for b in ir.blocks}
    n_in = ir.num_inputs
    for pair in ir.input_output_aliases:
        in_idx, out_idx = int(pair[0]), int(pair[1])
        inp = by_index.get(in_idx)
        out = by_index.get(n_in + out_idx)
        if inp is None or out is None or not (inp.blocked and
                                              out.blocked):
            report.add(Diagnostic(
                rule="ZS-K005", severity="error", where=where,
                message=f"input_output_aliases {in_idx}->{out_idx} on "
                        f"an operand without a windowed BlockSpec — "
                        f"liveness cannot be proven disjoint"))
            continue
        try:
            pts = list(_grid_points(ir, min(_GRID_SWEEP_CAP, 1024)))
            reads = [_window_range(
                _eval_index_map(ir, inp, ids, scalar_args),
                inp.block_shape) for ids in pts]
            writes = [_window_range(
                _eval_index_map(ir, out, ids, scalar_args),
                out.block_shape) for ids in pts]
        except _Uninterpretable as e:
            report.add(Diagnostic(
                rule="ZS-K005", severity="error", where=where,
                message=f"aliased index maps not statically "
                        f"evaluable: {e}"))
            continue
        for t, w in enumerate(writes):
            clash = next((t2 for t2 in range(t + 1, len(reads))
                          if _ranges_overlap(w, reads[t2])), None)
            if clash is not None:
                report.add(Diagnostic(
                    rule="ZS-K005", severity="error", where=where,
                    message=f"aliased output window written at grid "
                            f"step {t} overlaps the input window read "
                            f"at later step {clash} "
                            f"({in_idx}->{out_idx}) — the write "
                            f"destroys a live input block"))
                break


# ----------------------------------------------------------------------
# entry points
# ----------------------------------------------------------------------
def lint_kernel_ir(ir: KernelIR, *, where: str | None = None,
                   scalar_args: Iterable = (),
                   max_steps: int = 96) -> Report:
    """Verify one extracted kernel IR.  ``scalar_args`` supplies
    concrete values for scalar-prefetch operands (page tables, length
    vectors) so data-dependent index maps are evaluable; operands the
    body reads as values come from ``ir.operand_values``."""
    report = Report()
    where = where or ir.name
    scalar_args = tuple(scalar_args)

    _check_contract_shape(ir, report, where)
    _check_output_streaming(ir, report, where, scalar_args)
    _check_aliases(ir, report, where, scalar_args)
    if ir.contract is not None and ir.contract.managed_dma:
        _analyze_managed(ir, report, where, max_steps)

    report.meta = {"kernel": ir.name, "grid": list(ir.grid),
                   "steps": ir.total_steps}
    return report


def lint_kernels(families=None, *, space=None, backend: str = "interpret",
                 max_steps: int = 96) -> Report:
    """Sweep the kernel families across a tuning space and verify every
    emitted ``pallas_call``.

    Traces the public ``ops.*`` entry points (so the verifier sees the
    exact IR serving dispatches) for every feasible INTERPRET_SPACE
    candidate, runs :func:`lint_kernel_ir` on each, and returns one
    deduplicated :class:`Report`.  ``report.meta`` carries
    ``kernels_verified`` / ``zs_k_errors`` — the counters
    ``BENCH_analysis.json`` gates on.
    """
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.plan import KernelConfig
    from repro.quant import QTensor
    from repro.tune.space import INTERPRET_SPACE, Problem

    space = space or INTERPRET_SPACE
    picks = tuple(families or KERNEL_FAMILIES)
    unknown = set(picks) - set(KERNEL_FAMILIES)
    if unknown:
        raise ValueError(f"unknown kernel families: {sorted(unknown)}; "
                         f"expected a subset of {KERNEL_FAMILIES}")

    report = Report()
    verified = 0
    per_family: dict = {}

    def run(family, irs, scalar_args=()):
        nonlocal verified
        for ir in irs:
            sub = lint_kernel_ir(ir, scalar_args=scalar_args,
                                 max_steps=max_steps)
            report.extend(sub)
            verified += 1
            per_family[family] = per_family.get(family, 0) + 1

    def matmul_cfg(cand, **over):
        kw = dict(backend=backend, bm=cand.bm, bn=cand.bn, bk=cand.bk,
                  variant=cand.variant, slots=cand.slots,
                  grid_order=cand.grid_order)
        kw.update(over)
        return KernelConfig(**kw)

    if "zero_stall" in picks:
        prob = Problem("matmul", 32, 32, 32, dtype_bytes=4)
        a = jnp.ones((32, 32), jnp.float32)
        b = jnp.ones((32, 32), jnp.float32)
        stack = jnp.ones((3, 32, 32), jnp.float32)
        for cand in space.candidates(prob):
            run("zero_stall", trace_kernel_irs(
                ops.matmul, a, b, config=matmul_cfg(cand)))
            # the last layer of a stack, read in place
            run("zero_stall", trace_kernel_irs(
                ops.matmul, a, stack, layer=2, config=matmul_cfg(cand)))

    if "grouped" in picks:
        prob = Problem("grouped_matmul", 16, 16, 16, dtype_bytes=4,
                       groups=2)
        a = jnp.ones((2, 16, 16), jnp.float32)
        b = jnp.ones((2, 16, 16), jnp.float32)
        for cand in space.candidates(prob):
            run("grouped", trace_kernel_irs(
                ops.grouped_matmul, a, b,
                config=matmul_cfg(cand, grid_order="ijk")))

    if "quantized" in picks:
        prob = Problem("matmul", 32, 32, 32, dtype_bytes=1)
        x = jnp.ones((32, 32), jnp.float32)
        qw = QTensor(jnp.ones((32, 32), jnp.int8),
                     jnp.ones((1, 32), jnp.float32), fmt="int8")
        for cand in space.candidates(prob):
            run("quantized", trace_kernel_irs(
                ops.quantized_matmul, x, qw, config=matmul_cfg(cand)))
        gprob = Problem("grouped_matmul", 16, 16, 16, dtype_bytes=1,
                        groups=2)
        gx = jnp.ones((2, 16, 16), jnp.float32)
        gqw = QTensor(jnp.ones((2, 16, 16), jnp.int8),
                      jnp.ones((2, 1, 16), jnp.float32), fmt="int8")
        for cand in space.candidates(gprob):
            run("quantized", trace_kernel_irs(
                ops.quantized_grouped_matmul, gx, gqw,
                config=matmul_cfg(cand, grid_order="ijk")))

    if "attention" in picks:
        q = jnp.ones((1, 2, 16, 8), jnp.float32)
        tiles = [t for t in space.tile_options if t <= 16]
        for bq, bkv in itertools.product(tiles, tiles):
            cfg = KernelConfig(backend=backend, bq=bq, bkv=bkv)
            run("attention", trace_kernel_irs(
                ops.attention, q, q, q, config=cfg))
        # paged decode: page-table gather index maps need the concrete
        # table, supplied as scalar_args
        B, H, KV, D, P, ps, T = 2, 4, 2, 8, 6, 4, 3
        qd = jnp.ones((B, H, D), jnp.float32)
        pool = jnp.ones((P, ps, KV, D), jnp.float32)
        pt = (jnp.arange(B * T, dtype=jnp.int32) % P).reshape(B, T)
        lens = jnp.full((B,), ps * T, jnp.int32)
        run("attention", trace_kernel_irs(
            ops.paged_attention, qd, pool, pool, pt, kv_lens=lens,
            config=KernelConfig(backend=backend)),
            scalar_args=(np.asarray(pt).reshape(-1),
                         np.full((B,), ps * T, np.int32)))

    out = report.dedupe()
    zs_k_errors = sum(d.count for d in out.errors
                      if d.rule.startswith("ZS-K"))
    out.meta.update({
        "kernels_verified": verified,
        "families": dict(sorted(per_family.items())),
        "zs_k_errors": zs_k_errors,
        "backend": backend,
    })
    return out
