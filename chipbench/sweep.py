#!/usr/bin/env python3
"""Find an open-loop cell's knee: one program set-up, then one measured
window per offered rate, in one process on the chip.

    python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 0.5,0.7,0.9

Per rate it prints the requests offered, TTFT p50 and p90, how long the
backlog took to drain after the window closed, and how the queue wait of
the window's last third compares with its first third (a backlog that
grows through the window makes that ratio climb).  The benchmark's own
runs never sweep: the rate is fixed in the traffic file.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO / "src"), str(REPO)]


def summarize(logs, t_close, t_end) -> dict:
    from chipbench.stats import percentile
    gs = sorted(logs.values(), key=lambda g: g.due)
    ttft = [(g.times[0] - g.due) * 1e3 for g in gs if g.times]
    qw = [((g.submit - g.due) + (g.queue_wait_s or 0.0)) * 1e3 for g in gs]
    third = max(len(qw) // 3, 1)
    return {"requests": len(gs), "unfinished": sum(not g.done for g in gs),
            "ttft_p50_ms": percentile(ttft, 50),
            "ttft_p90_ms": percentile(ttft, 90),
            "drain_s": t_end - t_close,
            "queue_wait_last_over_first": (
                percentile(qw[-third:], 50)
                / max(percentile(qw[:third], 50), 1e-3) if qw else None)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    from repro.kernels import ops

    from chipbench import bench
    cell = bench.load_cell(args.workload)
    if not cell.open_loop:
        raise SystemExit("a sweep is for open-loop cells")
    with ops.strict_fallbacks():
        ses = bench.Session(cell, args.seed)
        print(f"setup_s {ses.setup_s:.3f}", flush=True)
        for rate in (float(r) for r in args.rates.split(",")):
            traffic = copy.deepcopy(cell.traffic)
            traffic["arrivals"]["rate_per_s"] = rate
            logs, t0, t_close, t_end, _ = ses.window(args.seconds,
                                                     traffic=traffic)
            print(json.dumps({"rate_per_s": rate,
                              **summarize(logs, t_close, t_end)}),
                  flush=True)
        ses.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
