"""Host runtime: milliseconds of Python's collector (the program's
``host.gc`` spans) inside the window, per second of window."""

from chipbench.hostspans import window


def read(run):
    spans = window(run)
    if spans is None:
        return None
    lo, hi = run.t0 * 1e9, (run.t0 + run.seconds) * 1e9
    ns = sum(max(0.0, min(s[2], hi) - max(s[1], lo))
             for s in spans if s[0] == "host.gc")
    return ns / 1e6 / run.seconds
