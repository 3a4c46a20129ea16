"""hybrid_prefill_mfu_pct: FLOPs the served prefill results of a Granite
4.0-H hybrid require (each layer by its kind at each request's real tokens,
the head at its last position only; ``shapes_hybrid.py`` writes the count
out) over the device time of the program's prefill forward in the trace
times the chip's peak bf16 FLOP/s.  The forward is ``JaxExecutor._fwd``, a
jitted lambda, so its programs are named ``jit__lambda``.  Nothing (None)
without a trace, peaks, forward time or batches; otherwise a finite
number."""

import math

from shapes_hybrid import prefill_flops

PROGRAM = "jit__lambda"


def read(run):
    if run.trace is None or run.kind != "atomic" or not run.peaks:
        return None
    ns = run.trace.module_ns(lambda name: name.startswith(PROGRAM))
    lengths = [n for *_, ls in run.batches for n in ls]
    if ns <= 0 or not lengths:
        return None
    flops = sum(prefill_flops(run.model, n) for n in lengths)
    value = 100.0 * flops / (ns * 1e-9 * run.peaks["bf16_flops"])
    return float(value) if math.isfinite(value) else None
