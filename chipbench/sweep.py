"""Find the knee of an open-loop cell: one set-up, then one window per
offered rate, in one process.

    python3 chipbench/sweep.py --workload <name> --seed <n> \
        --seconds <s> --rates 1 1.5 2 2.5 3

Prints one JSON line per rate: the end-to-end numbers, the requests left
unfinished, and how far the queue and the generator fell behind.  The
benchmark's own runs never sweep: each cell offers the fixed rate in its
traffic file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import run  # noqa: E402
from chipbench.cellconfig import cell  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    a = ap.parse_args()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    run.use_compile_cache()
    from chipbench import harness

    workload, conf, mix = cell(a.workload)
    c = harness.Cell(conf, mix, a.seed)
    c.setup()
    for rate in a.rates:
        c.reset_records()
        mix["arrivals"]["rate_per_s"] = rate
        c.drive(c.traffic.window(a.seconds), a.seconds,
                drain_limit=mix["drain_limit_s"])
        e2e = harness.end_to_end(c, a.seconds)
        qw = sorted(r.prefill_start - r.due for r in
                    (c.records[i] for i in c.window_rids)
                    if r.prefill_start is not None)
        print(json.dumps({"rate": rate, **e2e,
                          "queue_wait_p50_s": qw[len(qw) // 2] if qw else None,
                          "queue_wait_max_s": qw[-1] if qw else None,
                          "decode_steps": len(c.engine.steps)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
