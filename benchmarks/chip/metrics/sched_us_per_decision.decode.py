"""sched_us_per_decision.decode: wall time inside the token scheduler's
hooks (arrivals, first batch, per-step admission) per decision of the loop,
in microseconds; the pacing wait is not in it."""


def read(run):
    w = run.window
    return 1e6 * w.pacer.sched_s / w.n_decisions if run.kind == "tokens" and w.n_decisions else None
