#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 11,12,... --control 3

For each seed: one whole run of the cell (the same code path as
``run.py``), then, for the first ``--control`` seeds, the control on the
very sample that run checked: the plain reference with its weights
rounded to fp8 (e4m3) put in the program's place, judged at each
position by the float32 reference through the harness's own verdict
(``check.check`` with the cell's limits), which has to come out not
correct.  Prints one JSON line per seed: the program's widest logit
gap and verdict, the control's, and the run's metrics.
``limits/<cell>.json`` records the limit set between the two, and
PERF.md the readings.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO / "src"), str(REPO)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)

    from chipbench import bench, check
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        seen: dict = {}
        out = bench.run(args.workload, seed, args.seconds, False,
                        log=lambda s: print(s, file=sys.stderr, flush=True),
                        inspect=seen)
        row = {"seed": seed, "correct": out["correct"],
               "program_gap": out["compared"]["logit_gap"]["value"],
               "requests_checked": len(seen["verdict"].sample),
               "tokens_checked": sum(len(g.tokens)
                                     for g in seen["verdict"].sample),
               "metrics": {k: v["value"] for k, v in out["metrics"].items()},
               "memory_peak_bytes": out["device"]["memory_peak_bytes"]}
        if i < args.control:
            ctl = check.check(seen["run"], seen["ref"], seen["dims"], seed,
                              control=True)
            row["control_gap"] = ctl.compared["logit_gap"]["value"]
            row["control_correct"] = ctl.correct
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
