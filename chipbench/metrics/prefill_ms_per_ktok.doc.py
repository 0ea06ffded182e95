"""Model step: device time of the jitted prefill program per 1024 prompt
tokens, over the traced prefills of 4k-16k documents (moves tpot_ms:
each batch-1 prefill holds up the batch's decode)."""

from chipbench.tracereduce import prefill_ms_per_ktok


def read(run):
    return prefill_ms_per_ktok(run)
