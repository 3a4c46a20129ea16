"""sched_scored_per_decision.prefill: (request, batch size) lines the Orloj
scheduler scored in the window (on arrival, on milestone re-scores and on
full recomputes; the program's ``SimResult.n_scored``) per batch decision
of the loop.  Nothing where the program does not count them."""


def read(run):
    w = run.window
    n = getattr(w.result, "n_scored", None)
    return n / w.n_decisions if run.kind == "atomic" and n and w.n_decisions else None
