"""Device: the share of device-idle time inside the scheduler's
``step()`` spans (traced window) that no program span below the
scheduler tick accounts for: the innermost program span over it is
``sched.step`` or none, in %.  It checks the instrumentation's
coverage: it speeds up nothing, and a perf change that removes idle
time under a named span raises it while ``tpot_ms`` falls."""

from chipbench.hostspans import UNATTRIBUTED, idle_by_span


def read(run):
    split = idle_by_span(run)
    total = sum(split.values()) if split else 0.0
    if not total:
        return None
    return 100.0 * sum(split.get(k, 0.0) for k in UNATTRIBUTED) / total
