"""setup_s: wall seconds from process start to the first offered request:
JAX and device init, weights, the latency profile, priors and warm-up."""


def read(run):
    return run.setup_s
