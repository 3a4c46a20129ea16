"""decode_mfu_pct: FLOPs of attention over the valid cache of the active
slots, over the window's decode steps, divided by the device time of the
decode step program (``DecodeJaxExecutor._step_impl``, jitted) in the trace
times the chip's peak bf16 FLOP/s."""

from shapes import decode_attention_cost

PROGRAM = "jit__step_impl"


def read(run):
    if run.trace is None or run.kind != "tokens" or not run.peaks:
        return None
    ns = run.trace.module_ns(lambda name: name.startswith(PROGRAM))
    if ns <= 0:
        return None
    flops = sum(decode_attention_cost(run.model, list(v))[0] for *_, v in run.steps)
    return 100.0 * flops / (ns * 1e-9 * run.peaks["bf16_flops"])
