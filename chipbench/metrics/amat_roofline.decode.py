"""Expert kernel: the chip's least time for the expert layer's needed
work (``work.expert_work``: routed experts' codes at the precision DBSC
asked, metadata, activations, FLOPs) over the summed device time of the
expert kernel's operations, in the traced decode executions, in %."""

from chipbench.tracereduce import executions
from chipbench.work import expert_work, least_time


def read(run):
    if run.trace is None:
        return None
    need = spent = 0.0
    for ex in executions(run.trace, "decode"):
        if ex["n"] is None or not ex["kernel_ns"]:
            continue
        need += least_time(*expert_work(run.dm, *run.plan[ex["n"]]),
                           run.peaks)
        spent += ex["kernel_ns"] / 1e9
    return 100.0 * need / spent if spent else None
