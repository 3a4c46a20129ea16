"""decode_attention_roofline_pct: the least time the chip could take for the
window's decode-attention calls (the larger of FLOPs over peak FLOP/s and
bytes over HBM bandwidth, with q, out and each active slot's valid K and V
counted once in bf16) over the device time of the Pallas kernel in the
trace.  At these shapes the bytes bound binds."""

from shapes import decode_attention_cost

KERNEL = "decode_attention"


def read(run):
    if run.trace is None or run.kind != "tokens" or not run.peaks:
        return None
    ns = run.trace.op_ns(lambda name: KERNEL in name)
    if ns <= 0:
        return None
    least = 0.0
    for *_, v in run.steps:
        flops, nbytes = decode_attention_cost(run.model, list(v))
        least += max(flops / run.peaks["bf16_flops"], nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ns * 1e-9)
