"""Engine charge path: milliseconds per decode step in ``charge.replay``,
the step's slice demand replayed into the simulated cache and the cost
ledger (``_charge_sync`` / ``_charge_async``)."""

from chipbench.hostspans import per_step_ms


def read(run):
    return per_step_ms(run, "charge.replay")
