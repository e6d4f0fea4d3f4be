"""Roofline-term extraction from compiled XLA artifacts.

The container is CPU-only, so wall-clock MFU cannot be measured; the
dry-run instead lowers + compiles every (arch x shape x mesh) cell and
this module derives the three roofline terms from the compiled module:

    compute term    = HLO_FLOPs / (chips * peak_FLOP/s)
    memory term     = HLO_bytes / (chips * HBM_bw)
    collective term = collective_bytes / (chips * link_bw)

`compiled.cost_analysis()` provides FLOPs / bytes for the *partitioned*
(per-device) module, so per-device figures are multiplied by `chips` to
get module totals before applying the formulas (the two conventions are
equivalent; we record both).  collective_bytes is not in cost_analysis:
we parse the post-partitioning HLO text and sum operand bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute, weighting all-reduce 2x (ring RS+AG lower bound).

Hardware constants (TPU v5e class, per chip): 197 TFLOP/s bf16,
819 GB/s HBM, ~50 GB/s/link ICI.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

__all__ = ["HW", "CollectiveStats", "RooflineReport", "analyze_compiled",
           "parse_collective_bytes", "model_flops"]


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float = 197e12   # bf16 FLOP/s per chip
    hbm_bw: float = 819e9        # B/s per chip
    link_bw: float = 50e9        # B/s per ICI link
    hbm_bytes: int = 16 * 2**30  # 16 GiB per chip (v5e)


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

# matches e.g. "bf16[16,1024,128]{2,1,0}" or "f32[]"
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")

# collective op kinds and their traffic weight (x operand bytes).
_COLLECTIVES = {
    "all-reduce": 2.0,          # ring reduce-scatter + all-gather
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}
_COLL_RE = re.compile(
    r"^\s*(?:%[\w.\-]+|ROOT\s+%?[\w.\-]+)\s*=\s*(.*?)\s"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", re.M)


def _shape_bytes(type_str: str) -> int:
    """Bytes of one HLO result type (handles tuples by summing)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict[str, float]
    count_by_kind: dict[str, int]

    @property
    def total_bytes(self) -> float:
        return sum(self.bytes_by_kind.values())


def parse_collective_bytes(hlo_text: str) -> CollectiveStats:
    """Sum weighted traffic bytes of every collective in (per-device) HLO.

    Traffic model: all-gather ~ the gathered output; reduce-scatter /
    all-to-all / permute ~ the input; all-reduce ~ 2x input (ring RS+AG).
    `-start` ops are counted and their matching `-done` skipped (async
    pairs would otherwise double-count).  Lines with typed operands only
    (pre-optimization HLO); the trip-count-aware analyzer in
    core.hlo_costs handles optimized modules.
    """
    bytes_by_kind: dict[str, float] = defaultdict(float)
    count_by_kind: dict[str, int] = defaultdict(int)
    for m in _COLL_RE.finditer(hlo_text):
        line = hlo_text[m.start():hlo_text.find("\n", m.start())]
        if re.search(r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
                     r"collective-permute)-done\(", line):
            continue
        kind = m.group(2)
        if kind == "all-gather":
            b = _shape_bytes(m.group(1))        # result (gathered) bytes
        else:
            paren = line[line.find("(", m.end(2) - m.start()) :]
            b = _shape_bytes(paren) or _shape_bytes(m.group(1))
        bytes_by_kind[kind] += _COLLECTIVES[kind] * b
        count_by_kind[kind] += 1
    return CollectiveStats(dict(bytes_by_kind), dict(count_by_kind))


@dataclasses.dataclass
class RooflineReport:
    name: str
    chips: int
    # module totals (per-device figures x chips)
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    collectives: CollectiveStats
    per_device_bytes_peak: float    # from memory_analysis (fits-in-HBM proof)
    model_flops_useful: float = 0.0
    hw: HW = dataclasses.field(default_factory=HW)

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / (self.chips * self.hw.peak_flops)

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / (self.chips * self.hw.hbm_bw)

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / (self.chips * self.hw.link_bw)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def roofline_fraction(self) -> float:
        """max(term)/sum(terms): 1.0 = perfectly overlapped single bound."""
        s = self.t_compute + self.t_memory + self.t_collective
        if s == 0:
            return 0.0
        return max(self.t_compute, self.t_memory, self.t_collective) / s

    @property
    def useful_flop_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — catches remat/redundancy waste."""
        if self.hlo_flops == 0:
            return 0.0
        return self.model_flops_useful / self.hlo_flops

    def row(self) -> dict:
        return {
            "cell": self.name,
            "chips": self.chips,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "roofline_fraction": self.roofline_fraction,
            "hlo_gflops": self.hlo_flops / 1e9,
            "hlo_gbytes": self.hlo_bytes / 1e9,
            "coll_gbytes": self.collective_bytes / 1e9,
            "useful_flop_ratio": self.useful_flop_ratio,
            "peak_device_bytes": self.per_device_bytes_peak,
        }


def _cost_get(cost: dict, *keys: str) -> float:
    for k in keys:
        if k in cost and cost[k] is not None:
            return float(cost[k])
    return 0.0


def analyze_compiled(name: str, compiled, chips: int, *,
                     model_flops_useful: float = 0.0,
                     hw: HW | None = None) -> RooflineReport:
    """Build a RooflineReport from a jax `Compiled` object.

    FLOPs/bytes/collective-bytes come from the trip-count-aware HLO
    analyzer (core.hlo_costs) because XLA's cost_analysis counts scan
    bodies once; the raw cost_analysis figures are kept for reference
    in `raw_cost`.
    """
    from repro.core.hlo_costs import analyze_hlo

    hw = hw or HW()
    cost = compiled.cost_analysis() or {}
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = ""
    hc = analyze_hlo(hlo)
    flops_dev = hc.flops
    bytes_dev = hc.bytes
    coll = CollectiveStats(dict(hc.collective_by_kind),
                           dict(hc.collective_count))

    mem = {}
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument": getattr(ma, "argument_size_in_bytes", 0),
            "output": getattr(ma, "output_size_in_bytes", 0),
            "temp": getattr(ma, "temp_size_in_bytes", 0),
            "generated_code": getattr(ma, "generated_code_size_in_bytes", 0),
        }
    except Exception:
        pass
    peak_dev = float(sum(mem.values())) if mem else 0.0

    rep = RooflineReport(
        name=name,
        chips=chips,
        hlo_flops=flops_dev * chips,
        hlo_bytes=bytes_dev * chips,
        collective_bytes=coll.total_bytes * chips,
        collectives=coll,
        per_device_bytes_peak=peak_dev,
        model_flops_useful=model_flops_useful,
        hw=hw,
    )
    rep.raw_cost = {"flops": _cost_get(cost, "flops"),
                    "bytes": _cost_get(cost, "bytes accessed"),
                    "memory_analysis": mem}
    return rep


def model_flops(n_params_active: float, tokens: float, *, train: bool = True) -> float:
    """Useful model FLOPs: 6*N*D for training, 2*N*D for inference."""
    return (6.0 if train else 2.0) * n_params_active * tokens
