"""host_ms_per_step.decode: mean over the window's decode steps of the wall
time from the step's virtual start to its completion, minus the ms the
program's DecodeJaxExecutor.step_time returned (its timed prefill of joins
and its timed decode step): slot seeding and release, the synthetic
values, dispatch outside the timers."""


def read(run):
    st = run.steps
    return sum(end - start - inner for start, end, inner, *_ in st) / len(st) if st else None
