"""Readings that set the limits of ``correct``, for several seeds in one
process.

    python3 chipbench/readings.py --workload <name> --seconds <s> \
        --seeds 11 12 13

Each seed serves one window of the cell at its own size and load (a
shorter window than a benchmark run's), then judges it, through the same
comparison and limits as a benchmark run, four ways:

* ``program``: the served tokens and routing, as a run does;
* ``control``: the float8 control in the program's place;
* ``critical_cleared``: the routing trace with every selection marked
  not critical, as a program that ran every expert MSB-only would report;
* ``wrong_experts``: the routing trace with each live selection moved to
  the next expert id, as a router that picked other experts would report.

Prints one JSON line per seed.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import run  # noqa: E402
from chipbench.cellconfig import benchmark, cell  # noqa: E402


def critical_cleared(plan):
    return [(ids, act, np.zeros_like(crit), boost)
            for ids, act, crit, boost in plan]


def wrong_experts(plan, n_experts: int):
    return [(np.where(ids < n_experts, (ids + 1) % n_experts, ids),
             act, crit, boost) for ids, act, crit, boost in plan]


def readings(c, result) -> dict:
    """Every way of judging one served window: {way: {number: value}}."""
    out = {}
    plan = c.plan_log
    for way in ("program", "control", "critical_cleared", "wrong_experts"):
        if way == "critical_cleared":
            c.plan_log = critical_cleared(plan)
        elif way == "wrong_experts":
            c.plan_log = wrong_experts(plan, c.dm["experts"])
        line = run.judge(c, result, control=way == "control")
        c.plan_log = plan
        out[way] = {k: v["value"] for k, v in line["checks"].items()}
        out[way]["correct"] = line["correct"]
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args()
    bench = benchmark()
    workload, conf, mix = cell(a.workload)
    run.use_compile_cache()
    for seed in a.seeds:
        served = run.serve(workload, conf, mix, seed, a.seconds, False, bench,
                           t_start=time.perf_counter())
        if served is None:
            return 2
        print(json.dumps({"workload": a.workload, "seed": seed,
                          "failed": served[1]["failed"],
                          "attempted": served[1]["attempted"],
                          **readings(*served)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
