"""Scheduler: 90th percentile of the wait from a request's due time to
the start of its prefill (harness wall clock), over the window."""

from chipbench.traffic import percentile


def read(run):
    w = [1e3 * (r.prefill_start - r.due) for r in run.requests
         if r.prefill_start is not None]
    return percentile(w, 90) if w else None
