"""Whole step: the chip's least time for a decode step's needed work
(``work.step_work``) over the host-clock time of ``decode_batch``
(dispatch, device step, outputs, and the charge path), summed over the
window's decode steps, in %."""

from chipbench.work import least_time, step_work


def read(run):
    need = spent = 0.0
    for st, plan in zip(run.steps, run.plan):
        need += least_time(*step_work(run.dm, *plan, st["kv_len"]),
                           run.peaks)
        spent += st["t1"] - st["t0"]
    return 100.0 * need / spent if spent else None
