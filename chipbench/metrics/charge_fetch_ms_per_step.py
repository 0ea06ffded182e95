"""Engine charge path: milliseconds per decode step in ``charge.fetch``,
the routing aux copied to the host (``_StepTrace.from_aux``).  The
harness waits for the step's outputs before the charge path, so this is
the copy alone."""

from chipbench.hostspans import per_step_ms


def read(run):
    return per_step_ms(run, "charge.fetch")
