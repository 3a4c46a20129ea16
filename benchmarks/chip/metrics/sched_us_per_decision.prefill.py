"""sched_us_per_decision.prefill: wall time inside the Orloj scheduler's
hooks (arrivals, batch choice, completion feedback) per batch decision of
the loop, in microseconds; the pacing wait is not in it."""


def read(run):
    w = run.window
    return 1e6 * w.pacer.sched_s / w.n_decisions if run.kind == "atomic" and w.n_decisions else None
