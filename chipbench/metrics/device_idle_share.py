"""Device: share of the scheduler's ``step()`` spans in which no
operation ran on the device (waiting for arrivals is left out), over the
traced window, in %."""

from chipbench.tracereduce import busy, intersect, length, union


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    steps = union((s[1], s[2]) for s in run.trace.spans
                  if s[0] == "sched_step")
    if not steps:
        return None
    return 100.0 * (1.0 - length(intersect(busy(run.trace), steps))
                    / length(steps))
