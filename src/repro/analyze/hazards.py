"""Schedule hazard checker: prove a config's revolving buffer is safe.

The paper's zero-stall claim is *structural*: the N-slot revolving
buffer never lets the DMA engine write a slot whose operands a compute
step still needs, and the ZONL sequencer issues the tile nest with
zero control overhead.  Both are properties of the schedule, not the
data — so this module proves them by symbolic execution instead of
observing them in benchmarks (`repro.obs` can only flag a stall after
the fact).

:func:`simulate_schedule` replays the slot protocol of the kernels
(``kernels.zero_stall_matmul``: prologue primes slots ``0..N-1``, step
``t >= 1`` prefetches step ``t+N-1`` into slot ``(t-1) % N``) against
an *independent* resident-slot machine: each slot remembers which
step's operands it holds; a DMA issued concurrently with compute into
a slot whose operands are not yet consumed is the exact stall/
corruption condition the paper's Dobu hyperbanks eliminate.  The
checker is deliberately duck-typed over ``(slots, overlap)`` so it can
also reject *mutated* configs (e.g. ``slots=1`` with overlapping
DMA/compute phases) that :class:`repro.plan.KernelConfig` validation
refuses to construct.

:func:`check_config` runs the full per-config battery: schedule
simulation, cross-check against ``core.pipeline.RevolvingSchedule
.conflict_free()``, the bank-level Dobu mapping, VMEM footprint vs
:class:`~repro.core.cyclemodel.TpuParams` budgets, and (for small
grids) the ZONL sequencer-vs-unrolled trace equivalence.
"""

from __future__ import annotations

import math

from repro.analyze.diagnostics import Diagnostic
from repro.core.cyclemodel import SNITCH_CONFIGS, TpuParams, TpuPipelineModel
from repro.core.loopnest import matmul_nest
from repro.core.pipeline import RevolvingSchedule

__all__ = ["simulate_schedule", "check_config", "bank_access_pattern"]

#: Grid sizes above this are spot-checked by closed form only (the
#: sequencer trace is O(total issued instructions)).
_SEQ_TRACE_CAP = 4096

#: VMEM fraction the tuner budgets for the revolving buffers (the
#: compiler needs the rest for spills and the output window) — keep in
#: sync with ``repro.tune.space.KernelSpace(vmem_fraction=...)``.
_VMEM_FRACTION = 0.5


def _overlap_of(variant: str | None, slots: int) -> bool:
    """Does the schedule issue DMA concurrently with compute?

    The kernels overlap whenever they run the revolving buffer
    (``variant="dobu"`` / ``slots >= 2``); the serialized baseline
    (``variant="single"``) waits for compute before reusing its slot.
    A *mutated* config claiming "dobu" with one slot is exactly the
    hazard this checker exists to reject.
    """
    if variant is not None:
        return variant == "dobu"
    return slots >= 2


def simulate_schedule(steps: int, slots: int, *,
                      overlap: bool | None = None,
                      where: str = "schedule") -> list[Diagnostic]:
    """Symbolically execute the revolving-buffer slot protocol.

    Maintains ``resident[slot] = step`` (whose operands the slot
    holds) and a consumed set; every DMA issue is hazard-checked
    against the slots still live, every compute checked against the
    slot's resident step.  Emits:

    * ``ZS-S001`` (error)  — DMA-in targets a slot holding operands a
      step still needs (slot-reuse hazard: the paper's stall).
    * ``ZS-S002`` (info)   — serialized single-buffer schedule (safe
      but stalls by design: the Base32fc baseline).
    * ``ZS-S003`` (error)  — compute consumes a slot that was never
      primed with its operands (schedule underflow).
    """
    if steps < 1 or slots < 1:
        return [Diagnostic(
            rule="ZS-S003", severity="error", where=where,
            message=f"degenerate schedule (steps={steps}, slots={slots})",
            hint="steps and slots must both be >= 1")]
    if overlap is None:
        overlap = slots >= 2
    diags: list[Diagnostic] = []
    resident: dict[int, int] = {}   # slot -> step whose operands it holds
    consumed: set[int] = set()

    def dma(step: int, during_compute: int | None) -> None:
        slot = step % slots
        held = resident.get(slot)
        live = (held is not None and held not in consumed
                and (during_compute is None or held >= during_compute))
        if during_compute is not None and held == during_compute:
            live = True             # DMA racing the step being computed
        if live:
            diags.append(Diagnostic(
                rule="ZS-S001", severity="error", where=where,
                message=(f"prefetch of step {step} overwrites slot {slot} "
                         f"while step {held}'s operands are still being "
                         f"consumed (DMA/compute slot-reuse hazard)"),
                hint="use slots >= 2 (variant='dobu') or serialize the "
                     "DMA (variant='single')"))
        resident[slot] = step

    # prologue: prime every slot before compute starts (revolving
    # buffer), or just step 0 (serialized / mutated single-slot)
    primed = min(slots, steps) if overlap else 1
    for s in range(primed):
        dma(s, during_compute=None)

    for t in range(steps):
        # concurrent prefetch issued while step t computes
        if overlap:
            look = slots - 1 if slots > 1 else 1
            nxt = t + look if (t > 0 or slots == 1) else None
            if nxt is not None and nxt < steps and nxt >= primed:
                dma(nxt, during_compute=t)
        # compute consumes slot t % slots
        slot = t % slots
        if resident.get(slot) != t:
            holds = ("nothing" if slot not in resident
                     else f"step {resident[slot]}")
            diags.append(Diagnostic(
                rule="ZS-S003", severity="error", where=where,
                message=(f"step {t} computes from slot {slot} which holds "
                         f"{holds} (operands never primed)"),
                hint="the prologue must prime steps 0..slots-1 before "
                     "compute starts"))
        consumed.add(t)
        if not overlap and t + 1 < steps:
            # serialized: the next DMA waits for this compute — safe,
            # but every step pays the full transfer latency
            dma(t + 1, during_compute=None)

    if not overlap and steps > 1 and not any(
            d.rule == "ZS-S002" for d in diags):
        diags.append(Diagnostic(
            rule="ZS-S002", severity="info", where=where,
            message=f"serialized single-buffer schedule: {steps} steps "
                    f"each stall on their own DMA (the conflicted baseline)",
            hint="use slots >= 2 to overlap DMA with compute"))
    return diags


def bank_access_pattern(slots: int, steps: int
                        ) -> list[tuple[set[int], set[int]]]:
    """Per-step (compute banks, DMA banks) under the Dobu mapping.

    Each slot's A/B staging buffers map to their own bank pair
    ``{2s, 2s+1}`` — the TPU-VMEM analogue of pinning each
    double-buffer half to its own hyperbank — and the accumulator
    lives in a dedicated bank ``2*slots``.  Disjointness of the two
    sets at every step is the structural bank-conflict-freedom the
    Dobu interconnect provides in silicon.
    """
    sched = RevolvingSchedule(steps=steps, slots=slots)
    acc_bank = 2 * slots
    pattern = []
    for ph in sched.phases():
        compute = {2 * ph.compute_slot, 2 * ph.compute_slot + 1, acc_bank}
        dma = (set() if ph.prefetch_slot is None
               else {2 * ph.prefetch_slot, 2 * ph.prefetch_slot + 1})
        pattern.append((compute, dma))
    return pattern


def check_config(cfg, key=None, *, params: TpuParams | None = None,
                 steps: int | None = None) -> list[Diagnostic]:
    """Full static battery for one kernel config (duck-typed).

    ``cfg`` needs ``bm/bn/bk`` and ``slots`` (or ``resolved_slots``)
    and optionally ``variant`` — a :class:`repro.plan.KernelConfig`,
    a :class:`repro.tune.Candidate` or any stand-in works.  ``key``
    (an :class:`repro.plan.OpKey` or None) supplies the problem shape
    and operand width; without it a single-tile grid is assumed.

    Beyond :func:`simulate_schedule`, emits:

    * ``ZS-S004`` — VMEM footprint over budget (warning above the
      tuner's 50% staging budget, error above the physical VMEM).
    * ``ZS-S005`` (error) — model divergence: the symbolic executor
      and ``RevolvingSchedule.conflict_free()`` disagree, or the
      bank-level Dobu mapping finds an overlap the slot-level model
      missed.
    * ``ZS-S007`` (error) — the ZONL sequencer trace diverges from the
      unrolled reference for this grid (zero-overhead bound violated).
    """
    params = params or TpuParams()
    variant = getattr(cfg, "variant", None)
    slots = getattr(cfg, "slots", None)
    if slots is None:
        slots = getattr(cfg, "resolved_slots", None)
    if slots is None:
        slots = 2 if variant == "dobu" else 1
    slots = int(slots)
    bm, bn, bk = (int(getattr(cfg, f)) for f in ("bm", "bn", "bk"))
    where = (key.to_str() if hasattr(key, "to_str")
             else f"config(bm={bm},bn={bn},bk={bk},slots={slots})")
    overlap = _overlap_of(variant, slots)

    if key is not None and getattr(key, "op", None) == "attention":
        return _check_attention_config(cfg, key, params=params)

    # grid size: per-shape when a key is given; without one, simulate
    # a steady-state grid long enough to exercise slot wraparound (a
    # 1-step schedule has nothing to prefetch and hides reuse hazards)
    if steps is None:
        if key is not None:
            gm = math.ceil(key.M / bm)
            gn = math.ceil(key.N / bn)
            gk = math.ceil(key.K / bk)
            steps = max(1, gm * gn * gk)
        else:
            gm = gn = gk = 1
            steps = max(4, 2 * slots + 2)
    else:
        gm, gn, gk = steps, 1, 1
    sim_steps = min(int(steps), 64)         # wraparound needs ~2N steps
    sim_steps = max(sim_steps, min(int(steps), 2 * slots + 2))

    diags = simulate_schedule(sim_steps, slots, overlap=overlap, where=where)

    # cross-check: symbolic executor vs the closed-form schedule model
    # (and its bank-level projection) must agree on conflict-freedom
    sim_clean = not any(d.rule == "ZS-S001" for d in diags)
    model_clean = RevolvingSchedule(steps=sim_steps, slots=slots,
                                    ).conflict_free() if overlap else True
    banks_clean = all(not (comp & dma) for comp, dma
                      in bank_access_pattern(max(slots, 1), sim_steps)
                      ) if slots >= 2 else not overlap
    if overlap and (sim_clean != model_clean or
                    (slots >= 2 and sim_clean != banks_clean)):
        diags.append(Diagnostic(
            rule="ZS-S005", severity="error", where=where,
            message=(f"model divergence: symbolic execution says "
                     f"{'clean' if sim_clean else 'hazardous'}, "
                     f"RevolvingSchedule.conflict_free() says "
                     f"{model_clean}, bank mapping says {banks_clean}"),
            hint="core/pipeline.py and kernels/zero_stall_matmul must "
                 "implement the same slot protocol"))
    # silicon sanity: the paper's own configurations agree — the
    # overlapped schedule maps to a conflict-free Dobu config, the
    # serialized baseline to the conflicted 32-bank crossbar
    snitch = SNITCH_CONFIGS["zonl48dobu" if slots >= 2 else "base32fc"]
    if overlap and slots >= 2 and sim_clean != snitch.conflict_free:
        diags.append(Diagnostic(
            rule="ZS-S005", severity="error", where=where,
            message="Dobu silicon mapping disagrees with the schedule "
                    "simulation",
            hint="check SNITCH_CONFIGS conflict_free against the slot "
                 "protocol"))

    # VMEM footprint vs budget
    dtype_bytes = getattr(key, "dtype_bytes", 2) if key is not None else 2
    fp = TpuPipelineModel(params).vmem_footprint(
        bm, bn, bk, dtype_bytes=dtype_bytes, slots=max(slots, 1))
    if fp > params.vmem_bytes:
        diags.append(Diagnostic(
            rule="ZS-S004", severity="error", where=where,
            message=f"revolving buffers need {fp} B of VMEM; the chip "
                    f"grants a kernel {params.vmem_bytes} B",
            hint="shrink tiles or slots"))
    elif fp > params.vmem_bytes * _VMEM_FRACTION:
        diags.append(Diagnostic(
            rule="ZS-S004", severity="warning", where=where,
            message=f"revolving buffers need {fp} B of VMEM — over the "
                    f"{_VMEM_FRACTION:.0%} staging budget "
                    f"({int(params.vmem_bytes * _VMEM_FRACTION)} B); the "
                    f"compiler may spill",
            hint="shrink tiles or slots to leave headroom for the "
                 "output window"))

    # ZONL property: the sequencer issues the tile nest with zero
    # overhead — trace equivalence for small grids, closed form always
    nest = matmul_nest(gm, gn, gk)
    if nest.total_issued <= _SEQ_TRACE_CAP:
        try:
            seq = nest.sequencer_trace(max_cycles=nest.total_issued)
            if seq != nest.unrolled_trace():
                raise RuntimeError("sequencer trace diverged from the "
                                   "unrolled reference")
        except RuntimeError as e:
            diags.append(Diagnostic(
                rule="ZS-S007", severity="error", where=where,
                message=f"grid ({gm},{gn},{gk}): {e}",
                hint="the tile nest no longer satisfies the "
                     "zero-overhead sequencer bound"))

    # a hazardous schedule repeats its hazard every step — report each
    # (rule, severity) once per config, keeping the first occurrence
    seen: set[tuple[str, str]] = set()
    deduped = []
    for d in diags:
        if (d.rule, d.severity) not in seen:
            seen.add((d.rule, d.severity))
            deduped.append(d)
    return deduped


def _check_attention_config(cfg, key, *, params: TpuParams
                            ) -> list[Diagnostic]:
    """Attention configs: flash working-set budget (grid pipeline is
    always double-buffered, so the slot protocol has nothing to
    reject; the footprint can still blow VMEM)."""
    bq = int(getattr(cfg, "bq", 128))
    bkv = int(getattr(cfg, "bkv", 128))
    head_dim = int(key.N)
    dtype_bytes = getattr(key, "dtype_bytes", 2)
    where = key.to_str()
    tiles = 2 * (bq + 2 * bkv) * head_dim * dtype_bytes
    acc = bq * head_dim * 4 + 2 * bq * 4
    fp = tiles + acc
    diags: list[Diagnostic] = []
    if fp > params.vmem_bytes:
        diags.append(Diagnostic(
            rule="ZS-S004", severity="error", where=where,
            message=f"flash working set needs {fp} B of VMEM; the chip "
                    f"grants a kernel {params.vmem_bytes} B",
            hint="shrink bq/bkv"))
    elif fp > params.vmem_bytes * _VMEM_FRACTION:
        diags.append(Diagnostic(
            rule="ZS-S004", severity="warning", where=where,
            message=f"flash working set needs {fp} B of VMEM — over the "
                    f"{_VMEM_FRACTION:.0%} staging budget",
            hint="shrink bq/bkv"))
    return diags
