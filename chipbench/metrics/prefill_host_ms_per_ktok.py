"""Engine charge path: host milliseconds of a prefill's charging
(``prefill.charge``) and warm-up transition (``prefill.warmup``, PCW) per
1024 prompt tokens (the ``prefill`` spans' ``tokens``), from the
window's start on."""

from chipbench.hostspans import window


def read(run):
    spans = window(run)
    if spans is None:
        return None
    tokens = sum(s[3]["tokens"] for s in spans if s[0] == "prefill")
    if not tokens:
        return None
    ns = sum(s[2] - s[1] for s in spans
             if s[0] in ("prefill.charge", "prefill.warmup"))
    return ns / 1e6 / (tokens / 1024)
