"""Model step (host side): milliseconds per decode step in the engine's
``decode.policy`` (residency masks and their copies to the device) and
``decode.launch`` (the jitted decode call's return) spans, from the
window's start on."""

from chipbench.hostspans import per_step_ms


def read(run):
    return per_step_ms(run, "decode.policy", "decode.launch")
