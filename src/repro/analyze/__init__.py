"""`repro.analyze` — static zero-stall verifier.

The paper's headline claims — zero-overhead loop nests, zero-conflict
memory — are *structural* properties of schedules and programs, so
they can be proven before anything runs (``repro.obs`` can only
observe a stall after the fact).  Three layers:

1. **Schedule hazards** (:func:`check_config`, :func:`simulate_schedule`)
   — symbolic execution of the N-slot revolving-buffer protocol for
   one kernel config: slot-reuse hazards, VMEM budgets, the Dobu bank
   mapping, the ZONL sequencer bound.
2. **Plan lint** (:func:`lint_plan`) — whole-`repro.plan.Plan`
   validation: tile legality, int8 accumulator safety, out_dtype
   safety, decode-path buffer depth, replica fault-policy pairing.
   ``ServeEngine(plan=..., validate=True)`` runs it at load time.
3. **Program lint** (:func:`lint_program`) — jaxpr walk over traced
   prefill/decode/loss programs: non-Pallas fallback matmuls, host
   sync points inside fused dispatches, fp32 upcasts on the quantized
   path, stale allowlist entries across full-family sweeps.
4. **Kernel-IR verification** (:func:`lint_kernels`) — trace each
   kernel family's ``pallas_call`` IR, replay the body's DMA/compute
   events per grid step, and prove the emitted kernel realizes the
   schedule layers 1–2 reason about: residency timeline, prefetch
   look-ahead, VMEM bank pattern, HBM streaming order, alias liveness.

``scripts/analyze.py`` runs all four over the model-family configs
(``--kernels`` selects layer 4's INTERPRET_SPACE sweep); CI gates on
it.  Rule ids (``RULES``) are stable API.
"""

from __future__ import annotations

from repro.analyze.diagnostics import SEVERITIES, Diagnostic, Report
from repro.analyze.driver import FAMILY_ARCHS, analyze_arch, analyze_families
from repro.analyze.hazards import bank_access_pattern, check_config, simulate_schedule
from repro.analyze.kernel_lint import (
    KERNEL_FAMILIES,
    KernelIR,
    lint_kernel_ir,
    lint_kernels,
    trace_kernel_irs,
)
from repro.analyze.plan_lint import lint_cluster, lint_page_geometry, lint_plan
from repro.analyze.program_lint import DEFAULT_ALLOW, check_allowlist, lint_program

__all__ = [
    "Diagnostic", "Report", "SEVERITIES", "RULES",
    "check_config", "simulate_schedule", "bank_access_pattern",
    "lint_plan", "lint_page_geometry", "lint_cluster", "lint_program",
    "check_allowlist", "DEFAULT_ALLOW",
    "KERNEL_FAMILIES", "KernelIR", "trace_kernel_irs", "lint_kernel_ir",
    "lint_kernels",
    "FAMILY_ARCHS", "analyze_arch", "analyze_families",
]

#: rule id -> (default severity, layer, paper property / contract it
#: verifies).  Mirrored as the rule-catalog table in
#: docs/ARCHITECTURE.md; ids are stable (tests and CI gate on them).
RULES = {
    "ZS-S001": ("error", "schedule",
                "zero-conflict buffering: DMA-in never overwrites a slot "
                "whose operands a step still needs"),
    "ZS-S002": ("info", "schedule",
                "serialized single-buffer baseline (stalls by design — "
                "the Base32fc analogue)"),
    "ZS-S003": ("error", "schedule",
                "prologue completeness: every compute step's operands "
                "are primed before it issues"),
    "ZS-S004": ("warning", "schedule",
                "revolving buffers + accumulator fit the VMEM staging "
                "budget (double buffering trades memory for stalls)"),
    "ZS-S005": ("error", "schedule",
                "model coherence: symbolic execution, the closed-form "
                "schedule and the Dobu bank mapping agree"),
    "ZS-S007": ("error", "schedule",
                "ZONL: the sequencer issues the tile nest in exactly "
                "total_issued cycles (zero control overhead)"),
    "ZS-S008": ("error", "schedule",
                "paged KV: the per-slot page table covers max_len "
                "(capacity = table_len * page_size tokens)"),
    "ZS-L001": ("error", "plan", "every plan OpKey is resolvable"),
    "ZS-L002": ("error", "plan",
                "entry backend does not contradict the plan backend"),
    "ZS-L003": ("warning", "plan",
                "tiles never exceed the padded bucket dims (no pure "
                "zero-padding work)"),
    "ZS-L004": ("error", "plan",
                "int8 entries accumulate in int32, never int8"),
    "ZS-L005": ("warning", "plan", "out_dtype is a safe output type"),
    "ZS-L006": ("warning", "plan",
                "decode-hot GEMMs run the revolving buffer (slots >= 2)"),
    "ZS-L007": ("warning", "plan",
                "entry quant mode agrees with the plan quant mode"),
    "ZS-L008": ("error", "plan",
                "paged KV: page_size tiles every attention entry's KV "
                "block (bkv % page_size == 0)"),
    "ZS-L009": ("error", "plan",
                "every serving replica executes one plan (all "
                "Plan.fingerprint()s equal — divergent configs make "
                "tokens placement-dependent)"),
    "ZS-F001": ("warning", "plan+policy",
                "transient failures get at least one in-place retry"),
    "ZS-F002": ("error", "plan+policy", "retry backoff is well-formed"),
    "ZS-F003": ("warning", "plan+policy",
                "replica restarts resolve configs by lookup, not by "
                "re-tuning"),
    "ZS-F004": ("error", "plan+policy",
                "router fault policy bounds total re-queue backoff "
                "below the request timeout"),
    "ZS-P001": ("error", "program",
                "every matmul routes through the zero-stall kernels "
                "(no silent jnp fallback)"),
    "ZS-P002": ("error", "program",
                "no host sync points inside the fused K-step dispatch"),
    "ZS-P003": ("warning", "program",
                "the quantized path never dequantizes into a "
                "full-precision matmul"),
    "ZS-P004": ("warning", "program",
                "the fallback allowlist stays live: every sanctioned "
                "site still exists across the full-family sweep"),
    "ZS-K001": ("error", "kernel-ir",
                "kernel/config schedule coherence: the IR-derived "
                "residency timeline matches RevolvingSchedule, the "
                "hazard simulation and the declared contract"),
    "ZS-K002": ("error", "kernel-ir",
                "no overlapping VMEM windows across in-flight grid "
                "steps (a DMA never lands in a slot a step still "
                "reads)"),
    "ZS-K003": ("error", "kernel-ir",
                "the derived compute/DMA access pattern stays "
                "bank-disjoint under the Dobu mapping"),
    "ZS-K004": ("error", "kernel-ir",
                "HBM streaming: the grid walk never revisits an "
                "output block after eviction (accumulation runs are "
                "contiguous)"),
    "ZS-K005": ("error", "kernel-ir",
                "input_output_aliases never overwrite an input window "
                "a later grid step still reads"),
    "ZS-K006": ("error", "kernel-ir",
                "every manual DMA reads a window inside its source "
                "operand (a row offset into a stacked weight stays "
                "inside the stack)"),
}
