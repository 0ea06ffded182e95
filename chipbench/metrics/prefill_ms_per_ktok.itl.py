"""Model step: device time of the jitted prefill program per 1024 prompt
tokens, over the traced prefills (moves the 95th-percentile gap between
tokens: in the chat mix that gap holds a prefill)."""

from chipbench.tracereduce import prefill_ms_per_ktok


def read(run):
    return prefill_ms_per_ktok(run)
