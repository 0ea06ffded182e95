"""Scheduler: 90th percentile of the time from a request's due time to
its first token, over the window's finished requests (harness wall
clock), as ``ttft_p90_ms`` defines it.  Where the slots fill now and
then, this tail swings too widely to be held end to end; admission
decides it together with which gap between tokens holds a prefill, so
it moves the 95th-percentile gap."""

from chipbench.harness import end_to_end


def read(run):
    return end_to_end(run.cell, run.seconds)["ttft_p90_ms"]
