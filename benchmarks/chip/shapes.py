"""Operations and bytes that the served work requires, from shapes alone.

XLA's ``cost_analysis`` counts a scanned layer stack once, so it is not used.
Only the work a served result needs is counted: padding, and the LM head at
positions other than a request's last, are not required work.  Norms,
softmax, rotary positions and residual adds are left out; at these widths
they are well under 1% of the matrix products.
"""

from __future__ import annotations


def _dims(m: dict) -> tuple[int, int, int, int, int, int, int]:
    d, h, kv = m["d_model"], m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or d // h
    return m["n_layers"], d, h, kv, hd, m["d_ff"], m["vocab_size"]


def prefill_flops(m: dict, length: int) -> float:
    """FLOPs of one causal prefill of ``length`` real tokens: every layer at
    every real position, attention over the L(L+1)/2 causal pairs, and the
    LM head at the last position only."""
    n, d, h, kv, hd, ff, v = _dims(m)
    L = length
    proj = 2 * L * d * (h + 2 * kv) * hd + 2 * L * h * hd * d
    attn = 2 * 2 * h * hd * (L * (L + 1) // 2)
    mlp = 2 * L * d * ff * (3 if m["mlp"] == "swiglu" else 2)
    return float(n * (proj + attn + mlp) + 2 * d * v)


def decode_attention_cost(m: dict, valid: list[int], act_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode-attention step whose active slots attend
    over ``valid`` cache positions each: q.K and p.V for every head; q, out,
    and each slot's valid K and V read once, counted at ``act_bytes`` per
    element (bf16, the model's activation type)."""
    _, d, h, kv, hd, _, _ = _dims(m)
    pos = sum(valid)
    flops = 2 * 2 * h * hd * pos
    nbytes = act_bytes * (2 * len(valid) * h * hd + 2 * kv * hd * pos)
    return float(flops), float(nbytes)
