"""prefill_mfu_pct: FLOPs the served prefill results require (every layer
at each request's real tokens, the LM head at its last position only) over
the device time of the program's prefill forward in the trace times the
chip's peak bf16 FLOP/s.  The forward is ``JaxExecutor._fwd``, a jitted
lambda, so its programs are named ``jit__lambda``."""

from shapes import prefill_flops

PROGRAM = "jit__lambda"


def read(run):
    if run.trace is None or run.kind != "atomic" or not run.peaks:
        return None
    ns = run.trace.module_ns(lambda name: name.startswith(PROGRAM))
    if ns <= 0:
        return None
    flops = sum(prefill_flops(run.model, n) for *_, lengths in run.batches for n in lengths)
    return 100.0 * flops / (ns * 1e-9 * run.peaks["bf16_flops"])
