"""Attention kernel: the chip's least time for the absorbed decode
attention's needed work (the family's ``mla_work``: every valid latent
row read once, the queries and outputs, the score and value FLOPs) over
the summed device time of the ``mla_decode_attention`` kernel's
operations, in the traced decode executions, in %.  Reads nothing where
the cell's family has no ``mla_work`` or the trace holds no such op."""

import bisect

from chipbench.cellconfig import family
from chipbench.tracereduce import executions
from chipbench.work import least_time

KERNEL = "mla_decode_attention"


def read(run):
    if run.trace is None:
        return None
    work = getattr(family(run.dm["family"]), "mla_work", None)
    if work is None:
        return None
    kern = [o for o in run.trace.ops
            if o[0].split("/", 1)[1].startswith(KERNEL)]
    starts = [o[1] for o in kern]
    need = spent = 0.0
    for ex in executions(run.trace, "decode"):
        if ex["n"] is None:
            continue
        kt = 0.0
        for k in range(bisect.bisect_left(starts, ex["start"]), len(kern)):
            if kern[k][1] >= ex["end"]:
                break
            kt += min(kern[k][2], ex["end"]) - kern[k][1]
        if not kt:
            continue
        need += least_time(*work(run.dm, run.steps[ex["n"]]["kv_len"]),
                           run.peaks)
        spent += kt / 1e9
    return 100.0 * need / spent if spent else None
