"""Cycle-level performance/energy models.

Two instruments live here:

1. ``SnitchClusterModel`` — a cycle-level model of the paper's own
   evaluation platform (the Snitch cluster in its five configurations:
   Base32fc, Zonl32fc, Zonl64fc, Zonl64dobu, Zonl48dobu).  The paper
   evaluates in cycle-accurate RTL simulation; this container has no
   RTL, so we model the documented microarchitecture directly:

     * 8 single-issue compute cores, SSR-fed FPU, unroll-8 matmul
       kernel with peeled first/last K iterations (paper Fig. 1b);
     * single-level FREP (baseline) vs. zero-overhead loop nests
       (ZONL) via :mod:`repro.core.loopnest`;
     * a banked TCDM with interleaved layout, a DMA engine with a
       512-bit superbank port, and per-cycle arbitration between the
       core and DMA interconnect branches (32-bank configs) vs. the
       structurally conflict-free hyperbank routing of the Dobu
       interconnect (48/64-bank configs);
     * double-buffered block execution (DMA moves next/previous blocks
       while cores compute the current one).

   Free parameters (outer-loop overhead cycles, kernel startup cycles)
   are calibrated once against two published anchors (Table II
   utilizations at 32x32x32) and then *predict* the Fig. 5
   distributions; EXPERIMENTS.md reports predicted vs. published.

2. ``TpuPipelineModel`` — the TPU-native analogue used to reason about
   the Pallas kernels: an MXU/DMA overlap model for single- vs.
   double-buffered (dobu) VMEM staging, with per-grid-step control
   overhead for the pre-ZONL baseline (host-driven tile loop).

Energy is modeled per-component (compute / memory+interconnect /
control) with per-access energies chosen to reproduce the paper's
Table II power breakdowns; only *ratios* between configurations are
meaningful and that is all EXPERIMENTS.md quotes.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from repro.core.loopnest import Loop, LoopNest

__all__ = [
    "SnitchConfig",
    "SNITCH_CONFIGS",
    "SnitchClusterModel",
    "MatmulResult",
    "TpuParams",
    "TpuPipelineModel",
]


# ----------------------------------------------------------------------
# Snitch cluster configurations (paper Table I)
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SnitchConfig:
    name: str
    zonl: bool            # zero-overhead loop nests
    banks: int            # TCDM banks
    hyperbanks: int       # 1 = single address space (fc), 2 = dobu
    dobu: bool            # double-buffering-aware interconnect
    tcdm_kib: int
    # --- energy model (relative units, calibrated to Table II) ---
    # per-TCDM-access interconnect+bank energy [pJ]; larger crossbars
    # cost more per access (paper Sec. V-B / Gautschi et al.).
    e_access_pj: float
    # control (cores, I$, sequencer) power at full issue rate [mW].
    p_ctrl_mw: float

    @property
    def conflict_free(self) -> bool:
        """Zero-conflict memory subsystem?

        64 banks satisfy the worst-case RISC-V port demand
        ((3 reads + 1 write) * 8 cores * 2 = 64); 48 banks with the
        Dobu interconnect are conflict-free for double-buffered matmul
        (24-bank hyperbank >= 24 simultaneous core requests, DMA in the
        other hyperbank).
        """
        return self.banks >= 64 or (self.dobu and self.banks >= 48)


# Calibration notes:
#   * e_access_pj reproduces Table II "L1 Mem.+Interco." power ratios:
#     Base32fc 47.5+36.9 mW vs Zonl48dobu 36.9+36.9 mW, and the +12%
#     median energy of Zonl64fc (Fig. 5) from its big 64-port crossbar.
#   * p_ctrl_mw reproduces Ctrl 186.3 (base) / 189.2 (zonl) mW: the
#     sequencer adds ~3 mW but saves I$ fetches in steady state.
SNITCH_CONFIGS = {
    "base32fc": SnitchConfig("base32fc", False, 32, 1, False, 128, 1.00, 186.3),
    "zonl32fc": SnitchConfig("zonl32fc", True, 32, 1, False, 128, 1.00, 189.2),
    "zonl64fc": SnitchConfig("zonl64fc", True, 64, 1, False, 128, 1.90, 189.2),
    "zonl64dobu": SnitchConfig("zonl64dobu", True, 64, 2, True, 128, 1.12, 189.2),
    "zonl48dobu": SnitchConfig("zonl48dobu", True, 48, 2, True, 96, 0.95, 189.2),
}


@dataclasses.dataclass(frozen=True)
class MatmulResult:
    config: str
    M: int
    N: int
    K: int
    cycles: int
    useful_cycles: int          # FPU MAC-issue cycles (paper's utilization basis)
    stall_cycles_conflict: int
    overhead_cycles_loop: int
    dma_cycles: int
    power_mw: float

    @property
    def utilization(self) -> float:
        return self.useful_cycles / self.cycles

    @property
    def perf_gflops(self) -> float:
        # Paper accounting: peak = 8 DPGflop/s for 8 FPUs @ 1 GHz.
        return 8.0 * self.utilization

    @property
    def energy_eff_gflops_w(self) -> float:
        return self.perf_gflops / (self.power_mw * 1e-3)


class SnitchClusterModel:
    """Cycle model of the 8+1-core Snitch cluster running FP64 matmul."""

    N_CORES = 8
    UNROLL = 8                # paper footnote 2: actual implementations use 8
    FPU_LATENCY = 4           # RAW distance hidden by unrolling
    DMA_BYTES_PER_CYCLE = 64  # 512-bit port
    WORD = 8                  # FP64
    # Calibrated once against Table II (32x32x32 anchors: base 95.3%,
    # zonl48dobu 99.0%):
    KERNEL_STARTUP = 41       # SSR/FREP config + pipeline fill per tile kernel
    OUTER_OVERHEAD = 10       # per outer-loop iteration, non-ZONL (2 mgmt
                              # instrs + taken-branch refetch + addr bookkeeping)
    # L1 block tiling used for double-buffered execution (paper: layout
    # constrains each matrix to 8 banks / 32 KiB -> 32x32 FP64 blocks; a
    # 32x32x32 block is the common case, footnote to Sec. III-A).
    BLOCK = 32
    # Compute-core power at full utilization [mW] (Table II: 106.7 mW at
    # 95.3% util -> 112 mW at 100%).
    P_COMP_FULL = 112.0
    # Interconnect static+clock power [mW] (Table II column shared 36.9).
    P_INTERCO = 36.9

    def __init__(self, config: SnitchConfig):
        self.cfg = config

    # ------------------------------------------------------------------
    # Core issue timing for one (m_rows x N x K) slice on one core
    # ------------------------------------------------------------------
    def _core_cycles(self, m_rows: int, n: int, k: int) -> tuple[int, int, int]:
        """(issue_cycles, useful_cycles, loop_overhead) for one core.

        Kernel structure (paper Fig. 1b): collapsed outer loop over
        m_rows * ceil(n/unroll) groups; each group runs k steps of
        `u_eff` MAC instructions (first iteration fmul, last writes
        back through the store SSR — both useful FPU work).  When
        u_eff < FPU latency the accumulator RAW dependence stalls the
        pipe to FPU_LATENCY cycles per step.
        """
        if m_rows == 0 or n == 0 or k == 0:
            return 0, 0, 0
        useful = 0
        issue = 0
        n_outer = 0
        full_groups, rem = divmod(n, self.UNROLL)
        for u_eff, groups in ((self.UNROLL, full_groups), (rem, 1 if rem else 0)):
            if groups == 0:
                continue
            per_group_useful = k * u_eff
            per_group_issue = k * max(u_eff, self.FPU_LATENCY)
            useful += m_rows * groups * per_group_useful
            issue += m_rows * groups * per_group_issue
            n_outer += m_rows * groups
        overhead = 0 if self.cfg.zonl else n_outer * self.OUTER_OVERHEAD
        return issue + overhead + self.KERNEL_STARTUP, useful, overhead

    # ------------------------------------------------------------------
    # Bank-conflict model (32-bank configurations only)
    # ------------------------------------------------------------------
    def _conflict_probability(self, rng: np.random.Generator | None = None) -> float:
        """P(core stalls | DMA active this cycle), from bank geometry.

        Layout (from [6], adopted by the paper): A, B, C each constrained
        to one 8-bank superbank -> core reads spread over the 16 banks
        of A/B superbanks (+8 for C writeback).  The DMA moves next A/B
        and previous C through its 512-bit port, sweeping one superbank
        per cycle.  A core stalls if either of its two SSR reads hits
        the superbank the DMA occupies (the per-superbank mux grants the
        DMA, paper Sec. II).  Conflict-free configs return 0.
        """
        if self.cfg.conflict_free:
            return 0.0
        # With 32 banks = 4 superbanks and 6 live buffers (A,B,C x 2 for
        # double buffering, each pinned to an 8-bank superbank by the
        # conflict-minimizing layout of [6]), buffer placement cannot be
        # disjoint: current A(8)+B(8)+C(8) occupy 3 superbanks, leaving
        # one free.  Next-A lands in the free superbank; prev-C overlaps
        # the C superbank the cores touch only once per K cycles
        # (negligible); next-B must share a live read superbank.  The
        # DMA services its three streams round-robin, so during an
        # active DMA cycle the 512-bit beat (covering a whole superbank)
        # collides with the cores' B-stream reads 1/3 of the time, and
        # the per-superbank mux grants the DMA (paper Sec. II).
        return 1.0 / 3.0

    # ------------------------------------------------------------------
    # Whole-problem execution (double-buffered over 32^3 L1 blocks)
    # ------------------------------------------------------------------
    def matmul(self, M: int, N: int, K: int, *,
               include_dma: bool = True) -> MatmulResult:
        B = self.BLOCK
        mb, nb, kb = (math.ceil(M / B), math.ceil(N / B), math.ceil(K / B))

        total_issue = 0
        total_useful = 0
        total_loop_oh = 0
        total_dma = 0
        total_conflict = 0
        p_conf = self._conflict_probability() if include_dma else 0.0

        # Iterate L1 blocks; each block: rows split round-robin over 8
        # cores; cluster time = max over cores (barrier); DMA moves the
        # next A/B blocks and previous C block concurrently.
        for bm in range(mb):
            m_blk = min(B, M - bm * B)
            for bn in range(nb):
                n_blk = min(B, N - bn * B)
                for bk in range(kb):
                    k_blk = min(B, K - bk * B)
                    rows = [m_blk // self.N_CORES
                            + (1 if c < m_blk % self.N_CORES else 0)
                            for c in range(self.N_CORES)]
                    per_core = [self._core_cycles(r, n_blk, k_blk) for r in rows]
                    blk_issue = max(c for c, _, _ in per_core)
                    blk_useful = sum(u for _, u, _ in per_core)
                    blk_loop_oh = max((o for _, _, o in per_core), default=0)

                    dma_bytes = (m_blk * k_blk + k_blk * n_blk) * self.WORD
                    if bk == kb - 1:  # C writeback + next C prefetch
                        dma_bytes += 2 * m_blk * n_blk * self.WORD
                    dma_cyc = math.ceil(dma_bytes / self.DMA_BYTES_PER_CYCLE)

                    if include_dma:
                        if self.cfg.conflict_free:
                            # Dobu/64-bank: DMA fully overlapped, zero stalls.
                            blk_time = max(blk_issue, dma_cyc)
                            conflict = 0
                        else:
                            # Shared banks: while the DMA is active the losing
                            # core requests stall (superbank mux).
                            overlap = min(blk_issue, dma_cyc)
                            conflict = math.ceil(
                                overlap * p_conf / max(1e-9, 1 - p_conf))
                            blk_time = max(blk_issue + conflict, dma_cyc)
                    else:
                        blk_time = blk_issue
                        conflict = 0
                        dma_cyc = 0

                    total_issue += blk_time
                    total_useful += blk_useful
                    total_loop_oh += blk_loop_oh
                    total_dma += dma_cyc
                    total_conflict += conflict

        # utilization basis: useful MAC issue slots per core-cycle
        cycles = total_issue
        useful = math.ceil(total_useful / self.N_CORES)
        power = self._power(useful / cycles, p_conf if include_dma else 0.0)
        return MatmulResult(
            config=self.cfg.name, M=M, N=N, K=K,
            cycles=cycles, useful_cycles=useful,
            stall_cycles_conflict=total_conflict,
            overhead_cycles_loop=total_loop_oh,
            dma_cycles=total_dma,
            power_mw=power,
        )

    # ------------------------------------------------------------------
    def _power(self, util: float, p_conf: float) -> float:
        """Component power model calibrated to Table II (mW)."""
        p_comp = self.P_COMP_FULL * util
        # Memory accesses: 2 reads/MAC-cycle/core (+~1/K writes, folded in),
        # at ~2.1 GHz-normalized access rate; conflicts re-issue requests
        # (wasted energy, paper Sec. IV-B).
        access_rate = 2.0 * self.N_CORES * util * (1.0 + 0.5 * p_conf)
        p_mem = 2.31 * access_rate * self.cfg.e_access_pj  # mW @ 1 GHz
        return p_comp + p_mem + self.P_INTERCO + self.cfg.p_ctrl_mw

    # ------------------------------------------------------------------
    def loopnest_for_block(self, m_rows: int, n: int, k: int) -> LoopNest:
        """The per-core matmul nest as a LoopNest (for cross-validation)."""
        groups = max(1, n // self.UNROLL)
        return LoopNest(
            num_insts=self.UNROLL,
            loops=(
                Loop(trips=max(1, m_rows * groups), start=0,
                     end=self.UNROLL - 1, name="mn"),
                Loop(trips=max(1, k), start=0, end=self.UNROLL - 1, name="k"),
            ),
        )


# ----------------------------------------------------------------------
# TPU pipeline model (the adaptation target)
# ----------------------------------------------------------------------
#: Scoped VMEM every Pallas kernel asks Mosaic for
#: (``CompilerParams.vmem_limit_bytes``, see
#: :func:`repro.kernels.meta.compiler_params`) out of v5e's 128 MiB.
#: Mosaic's default grant is 16 MiB, which refuses tiles the tuner
#: admits; the tuner budgets its revolving buffers against this same
#: number, so every admitted tile compiles.
VMEM_LIMIT_BYTES = 96 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class TpuParams:
    """TPU v5e-class single-chip constants (public figures)."""

    peak_flops: float = 197e12        # bf16 FLOP/s
    peak_flops_int8: float = 394e12   # int8 OP/s (the MXU doubles rate
                                      # at 1-byte operands — v5e public
                                      # spec; precision shifts the
                                      # roofline, PAPERS.md)
    hbm_bw: float = 819e9             # B/s
    vmem_bytes: int = VMEM_LIMIT_BYTES  # what a kernel may claim
    ici_bw: float = 50e9              # B/s per link
    # control overhead per tile step when the tile loop is *not* run by
    # the grid sequencer (host-driven dispatch / fori_loop bookkeeping).
    host_step_overhead_s: float = 2e-6
    grid_step_overhead_s: float = 0.0  # ZONL analogue: zero

    def peak_for(self, dtype_bytes: int) -> float:
        """Compute roof for an operand width (1 byte -> int8 rate)."""
        return self.peak_flops_int8 if dtype_bytes == 1 else self.peak_flops


@dataclasses.dataclass(frozen=True)
class TpuKernelEstimate:
    name: str
    total_s: float
    compute_s: float
    dma_s: float
    overhead_s: float
    flops: float
    bytes_moved: float

    @property
    def mxu_utilization(self) -> float:
        return self.compute_s / self.total_s

    @property
    def roofline_bound_s(self) -> float:
        return max(self.compute_s, self.dma_s)

    @property
    def roofline_fraction(self) -> float:
        return self.roofline_bound_s / self.total_s


class TpuPipelineModel:
    """MXU/DMA overlap model for tiled Pallas matmul kernels.

    Mirrors the paper's two mechanisms on TPU terms:
      * ``double_buffered`` — Dobu analogue: tile t+1 DMA overlaps tile
        t compute (2-slot VMEM revolving buffer).  Per-step time is
        max(compute, dma).  ``slots`` generalizes this to the N-slot
        revolving buffer of the refactored kernels: a deeper ring
        averages HBM burstiness (the ``dma_cv`` jitter term) over more
        in-flight transfers; its cost is VMEM footprint (compute still
        blocks only on tile 0's fill — the extra slots prime in the
        background), so depth trades against the tile sizes that still
        fit the budget.
      * single-buffered (``slots=1``) — copy -> wait -> compute
        serialization (the "bank conflict" analogue: producer and
        consumer contend).
      * ``grid`` vs ``host`` loop — ZONL analogue: grid steps cost zero
        control; a host-driven tile loop pays dispatch per step.

    This model is the default cost oracle of :mod:`repro.tune`, which
    searches (bm, bn, bk, slots, grid order) per problem shape under
    the ``vmem_footprint`` budget and feeds the winner back into the
    Pallas kernels via ``ops.matmul(..., config="auto")``.
    """

    def __init__(self, params: TpuParams | None = None):
        self.p = params or TpuParams()

    def matmul(
        self,
        M: int, N: int, K: int,
        bm: int, bn: int, bk: int,
        *,
        dtype_bytes: int = 2,
        double_buffered: bool = True,
        grid_loop: bool = True,
        slots: int | None = None,
        dma_cv: float = 0.0,
        name: str = "matmul",
    ) -> TpuKernelEstimate:
        """Estimate one tiled matmul.

        ``slots`` overrides ``double_buffered`` when given (1 =
        serialized, >= 2 = revolving buffer of that depth).  ``dma_cv``
        is the coefficient of variation of per-tile HBM latency; it is
        charged to every configuration — a serialized pipeline exposes
        the full ``dma_cv * t_dma`` per step, while a depth-N ring
        averages it to ``dma_cv * t_dma / N`` (hyperbank-parity
        argument at arbitrary depth).  That slope versus the VMEM bill
        (deeper rings crowd out bigger tiles) is what makes buffer
        depth a non-trivial axis for :mod:`repro.tune`.
        """
        if slots is None:
            slots = 2 if double_buffered else 1
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        gm, gn, gk = map(math.ceil, (M / bm, N / bn, K / bk))
        steps = gm * gn * gk
        # per-step tile traffic: A tile + B tile; C written once per (m,n)
        a_b = (bm * bk + bk * bn) * dtype_bytes
        c_b = bm * bn * dtype_bytes
        t_dma_step = a_b / self.p.hbm_bw
        t_dma_c = c_b / self.p.hbm_bw
        # dtype widens/narrows BOTH terms: bytes through dtype_bytes,
        # compute through the per-width MXU roof — int8 halves the DMA
        # and doubles the rate, so the same tile shifts compute-bound.
        t_comp_step = (2 * bm * bn * bk) / self.p.peak_for(dtype_bytes)
        oh = self.p.grid_step_overhead_s if grid_loop else self.p.host_step_overhead_s

        if slots >= 2:
            # pipeline: compute blocks on tile 0's fill (deeper slots
            # prime in the background, overlapped with early steps),
            # then steps-1 overlapped steps of max(comp, dma) plus the
            # residual jitter a depth-N ring cannot hide, then the last
            # tile's compute drains.  Depth's cost is VMEM, not time —
            # the tuner's trade-off is slots vs the tile sizes that
            # still fit the budget.
            jitter = dma_cv * t_dma_step / slots
            total = (t_dma_step * (1.0 + dma_cv)
                     + (steps - 1) * (max(t_comp_step, t_dma_step) + jitter)
                     + t_comp_step + steps * oh
                     + gm * gn * t_dma_c)
        else:
            # serialized: full jitter exposure on every transfer
            total = (steps * (t_comp_step + t_dma_step * (1.0 + dma_cv) + oh)
                     + gm * gn * t_dma_c)

        flops = 2.0 * M * N * K
        bytes_moved = steps * a_b + gm * gn * c_b
        return TpuKernelEstimate(
            name=name,
            total_s=total,
            compute_s=steps * t_comp_step,
            dma_s=steps * t_dma_step + gm * gn * t_dma_c,
            overhead_s=steps * oh,
            flops=flops,
            bytes_moved=float(bytes_moved),
        )

    def vmem_footprint(self, bm: int, bn: int, bk: int, *, dtype_bytes: int = 2,
                       slots: int = 2) -> int:
        """Bytes of VMEM claimed by the revolving-buffer schedule."""
        return slots * (bm * bk + bk * bn) * dtype_bytes + bm * bn * 4  # fp32 acc
