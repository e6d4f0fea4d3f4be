#!/usr/bin/env python3
"""Do the engine's spans share the device trace's clock, and what does
tracing cost?

    python3 scripts/span_clock_check.py --workload <cell> --seed <n> \\
        [--seconds 51]

Makes one traced run of a benchmark cell (``chipbench.bench.run``, as
``chipbench/run.py --trace 1`` does) and keeps its profile until read.
From the profile's host plane it pairs each ``serve.step`` annotation
with its sink record, mapped onto the trace's clock by the offset the
benchmark's breakdown uses (the window annotation's start less the host
clock at the window's start), and reports the largest gap between the
two at either end.  It counts the spans per step, then times one span
on this host with tracing off, on, and on under a running profiler,
and gives the share of a step's wall time that a step's spans take when
on.  It adds how the stacked layer weights reached the matmul kernel
in the run's programs (``repro.kernels.ops.layer_weight_counts``: call
sites and one layer's weight bytes, read in place or sliced, and the
in-place share of the bytes in %).  Prints the run's notes on standard
error, then one JSON object (the result line, the check and the weight
reads) as the last line of standard output.
Needs the chip the cell asks for (exits 2 without it).
"""

from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO / "src"), str(REPO)]


def span_cost_s(n: int = 100_000) -> float:
    """Seconds per ``with obs.span(...)`` block in the current tracer
    state, best of three."""
    from repro import obs
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(n):
            with obs.span("serve.cost", step=1):
                pass
        best = min(best, (time.perf_counter() - t) / n)
    return best


def span_costs() -> dict:
    """One span's cost: tracing off, on, and on under a profiler trace
    with the benchmark's options."""
    import jax
    from repro import obs
    off = span_cost_s()
    obs.enable(sink=obs.ListSink())
    try:
        on = span_cost_s()
        with tempfile.TemporaryDirectory() as d:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(d, profiler_options=opts)
            try:
                profiled = span_cost_s()
            finally:
                jax.profiler.stop_trace()
    finally:
        obs.disable()
    return {"off_s": off, "on_s": on, "on_profiled_s": profiled}


def clock_check(profile_dir: str, run) -> dict:
    """Each ``serve.step`` annotation against its offset-mapped record."""
    from chipbench import trace
    notes = [h for h in trace.load(profile_dir, host_prefixes=("serve.",)).host
             if h[0] == "serve.step"]
    w0, w1 = run.trace_window
    off = w0 - run.t0 * 1e9
    spans = [s for s in run.spans if s.get("type") == "span"]
    steps = sorted((s for s in spans if s["name"] == "serve.step"),
                   key=lambda s: s["t"])
    if len(notes) != len(steps):
        raise SystemExit(f"{len(notes)} serve.step annotations but "
                         f"{len(steps)} records")
    d0 = [a - (s["t"] * 1e9 + off) for (_, a, _), s in zip(notes, steps)]
    d1 = [b - ((s["t"] + s["dur_s"]) * 1e9 + off)
          for (_, _, b), s in zip(notes, steps)]
    return {"steps": len(steps),
            "spans_per_step": len(spans) / max(len(steps), 1),
            "max_abs_start_ms": max(map(abs, d0), default=0.0) * 1e-6,
            "max_abs_end_ms": max(map(abs, d1), default=0.0) * 1e-6,
            "median_start_us": statistics.median(d0) * 1e-3 if d0 else 0.0,
            "median_end_us": statistics.median(d1) * 1e-3 if d1 else 0.0,
            "step_wall_s_mean": (statistics.fmean(s["dur_s"] for s in steps)
                                 if steps else 0.0)}


def weight_reads() -> dict:
    """The counts of layer weights read in place or sliced in the
    programs traced so far, and the in-place share of their bytes."""
    from repro.kernels import ops
    counts = ops.layer_weight_counts()
    total = counts["in_place.bytes"] + counts["sliced.bytes"]
    return {"weight_reads": counts,
            "in_place_byte_share": (100.0 * counts["in_place.bytes"]
                                    / total if total else None)}


def check(workload: str, seed: int, seconds: float, *, log=print,
          **run_kw) -> dict:
    """The traced run, then the clock check on its profile and the span
    costs; ``run_kw`` goes to ``bench.run`` (a test-size cell)."""
    from chipbench import bench, trace
    kept = Path(tempfile.mkdtemp(prefix="span_clock."))
    load = trace.load

    def keep(path, *a, **k):        # bench.run deletes the profile after
        shutil.copytree(path, kept, dirs_exist_ok=True)
        return load(path, *a, **k)
    trace.load = keep
    inspect: dict = {}
    try:
        out = bench.run(workload, seed, seconds, True, log=log,
                        inspect=inspect, **run_kw)
    finally:
        trace.load = load
    try:
        got = clock_check(str(kept), inspect["run"])
    finally:
        shutil.rmtree(kept, ignore_errors=True)
    cost = span_costs()
    got["span_cost"] = cost
    extra = cost["on_profiled_s"] - cost["off_s"]
    got["tracing_share_of_step"] = (got["spans_per_step"] * extra
                                    / got["step_wall_s_mean"]
                                    if got["step_wall_s_mean"] else None)
    return {"result": out, "check": got, **weight_reads()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    from chipbench import bench

    def log(line):
        print(line, file=sys.stderr, flush=True)
    try:
        got = check(args.workload, args.seed, args.seconds, log=log)
    except bench.NoChip as e:
        log(f"span_clock_check: {e}; nothing was run")
        return 2
    print(json.dumps(got), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
