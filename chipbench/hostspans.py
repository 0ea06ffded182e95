"""Read the program's own wall-clock spans for the per-layer metrics.

The engine keeps them in ``engine.host_trace`` (``repro.obs.hosttrace``):
a ring of ``(name, t0_ns, t1_ns, stats)`` records on
``time.perf_counter_ns``, the clock of the harness's own records
(``run.t0`` and ``run.steps[n]["t0"]``, in seconds).  A program without
it gives None everywhere, as does a ring that lost records of the
window.

To lay the spans on the profiler trace's clock, the offset is the median,
over the traced ``decode_batch`` harness spans, of the trace span's start
less ``run.steps[n]["t0"]``.
"""

from __future__ import annotations

import bisect
import statistics
from collections import defaultdict
from typing import Dict, List, Optional

from chipbench.tracereduce import busy, gaps, union

# Innermost spans under which device idle time is not put down to any
# piece of work: the scheduler tick itself, or no program span at all.
UNATTRIBUTED = ("sched.step", "")


def window(run) -> Optional[List[tuple]]:
    """The program's spans that began in or after the window, or None."""
    ht = getattr(run.cell.engine, "host_trace", None)
    if ht is None:
        return None
    t0 = int(run.t0 * 1e9)
    if ht.wrapped_since(t0):
        return None
    # A copy first: a collector pause appends to the ring.
    return [s for s in list(ht.ring) if s[1] >= t0]


def per_step_ms(run, *names: str) -> Optional[float]:
    """Milliseconds in the spans ``names`` per decode step (``decode``
    span) from the window's start on."""
    spans = window(run)
    if spans is None:
        return None
    n = sum(1 for s in spans if s[0] == "decode")
    if not n:
        return None
    return sum(s[2] - s[1] for s in spans if s[0] in names) / 1e6 / n


def offset_ns(run) -> Optional[float]:
    """Trace clock less host clock, in ns (see the module docstring)."""
    if run.trace is None:
        return None
    d = [s[1] - run.steps[s[3]["n"]]["t0"] * 1e9 for s in run.trace.spans
         if s[0] == "decode_batch" and s[3].get("n") is not None
         and s[3]["n"] < len(run.steps)]
    return statistics.median(d) if d else None


def innermost_split(intervals, spans) -> Dict[str, float]:
    """Length of ``intervals`` (merged ``(start, end)``) by the innermost
    of ``spans`` (``(name, start, end)``) covering each part: the one
    that began last, the shorter on a tie; "" where none covers it."""
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    out: Dict[str, float] = defaultdict(float)
    for a, b in intervals:
        cands = [s for s in spans[:bisect.bisect_left(starts, b)]
                 if s[2] > a]
        cuts = sorted({a, b} | {t for s in cands for t in s[1:3]
                                if a < t < b})
        for lo, hi in zip(cuts, cuts[1:]):
            mid = (lo + hi) / 2
            cover = [s for s in cands if s[1] <= mid < s[2]]
            name = max(cover, key=lambda s: (s[1], -s[2]))[0] \
                if cover else ""
            out[name] += hi - lo
    return dict(out)


def idle_by_span(run) -> Optional[Dict[str, float]]:
    """Device-idle ns inside the harness's ``sched_step`` spans of the
    traced window, by the innermost program span over each part."""
    tr = run.trace
    if tr is None or not tr.ops:
        return None
    spans, off = window(run), offset_ns(run)
    if spans is None or off is None:
        return None
    steps = union((s[1], s[2]) for s in tr.spans if s[0] == "sched_step")
    prog = [(s[0], s[1] + off, s[2] + off) for s in spans
            if s[2] + off > tr.t0 and s[1] + off < tr.t1]
    return innermost_split(gaps(busy(tr), steps), prog)
