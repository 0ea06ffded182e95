"""Model step: device time of the jitted prefill program per 1024 prompt
tokens, over the traced prefills (moves tokens per second)."""

from chipbench.tracereduce import prefill_ms_per_ktok


def read(run):
    return prefill_ms_per_ktok(run)
