"""The work a step needs, computed from shapes and routing alone.

Needed work is the same whatever implements it: it counts what the
computation has to read and do, not what today's program happens to
read.  A kernel that read only what is counted here at the chip's peak
would score 100%.

Expert layer, per decode step and layer: the codes and group metadata of
each expert that at least one active token was routed to, at 4 bits a
weight where no selection of that expert in the step is critical (DBSC
serves it MSB-only) and at 8 bits otherwise; metadata is an fp16 scale
and a zero-point of the code's width per group of ``group_size``; plus
each routed pair's activations in bf16 (input and output of both
projections) and its FLOPs.

Whole decode step: the expert layer, plus the rest of the step as the
cell's decoder family counts it (its ``dense_work``: the bf16 weights
outside the routed experts, the embedding rows, the cached rows of every
valid position of each active sequence, and the FLOPs of every matmul
and of attention over those rows).
"""

from __future__ import annotations

import json
import os

import numpy as np

from chipbench.cellconfig import family

BF16 = 2


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_time(nbytes: float, flops: float, pk: dict) -> float:
    """The chip's least time for the work: the larger of its two bounds."""
    return max(nbytes / pk["hbm_bytes_per_s"], flops / pk["bf16_flops_per_s"])


def expert_work(dm: dict, ids, active, critical) -> tuple[float, float]:
    """(bytes, FLOPs) of the expert layer in one decode step.

    ``ids``/``active``/``critical``: [L, npos, B, k] as the engine's
    recorder hook receives them.
    """
    d, F, E, g = dm["d"], dm["expert_ff"], dm["experts"], dm["group_size"]
    hb, lb = dm["high_bits"], dm["low_bits"]
    n_w = 3 * d * F                     # wi [d, 2F] + wo [F, d]
    n_g = n_w / g
    nbytes = flops = 0.0
    for li in range(ids.shape[0]):
        for pi in range(ids.shape[1]):
            a = active[li, pi]
            sel = ids[li, pi][a]
            routed = np.unique(sel)
            crit = np.unique(ids[li, pi][a & critical[li, pi]])
            n_hi = len(crit)
            n_lo = len(routed) - n_hi
            for n_e, bits in ((n_hi, hb), (n_lo, lb)):
                nbytes += n_e * (n_w * bits / 8 + n_g * (2 + bits / 8))
            pairs = sel.size
            nbytes += pairs * (d + 2 * F + F + d) * BF16
            flops += 2.0 * pairs * n_w
    return nbytes, flops


def step_work(dm: dict, ids, active, critical,
              kv_len) -> tuple[float, float]:
    """(bytes, FLOPs) of a whole decode step: the family's
    ``dense_work`` plus :func:`expert_work`.  ``kv_len`` holds each
    active sequence's valid KV rows after the step's write."""
    eb, ef = expert_work(dm, ids, active, critical)
    db, df = family(dm["family"]).dense_work(dm, kv_len)
    return eb + db, ef + df
