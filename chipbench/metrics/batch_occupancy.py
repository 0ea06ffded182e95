"""Scheduler: active slots before each decode step (the scheduler's slot
table), as a mean over the decode steps that start inside the window."""


def read(run):
    n = [sum(r is not None for r in s["rids"]) for s in run.steps
         if s["t0"] - run.t0 <= run.seconds]
    return sum(n) / len(n) if n else None
