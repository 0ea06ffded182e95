"""Model step: device time of one execution of the jitted decode program,
from the profiler trace, as a mean over the traced executions."""

from chipbench.tracereduce import executions


def read(run):
    if run.trace is None:
        return None
    ex = executions(run.trace, "decode")
    if not ex:
        return None
    return sum(e["end"] - e["start"] for e in ex) / len(ex) / 1e6
