"""Engine charge path (host): milliseconds per decode step in
``charge_decode_step``, timed after the step's outputs are on hand, so
only host work (routing replay, cache and ledger bookkeeping) counts."""


def read(run):
    d = [t1 - t0 for name, t0, t1 in run.spans if name == "charge_decode_step"]
    return 1e3 * sum(d) / len(d) if d else None
