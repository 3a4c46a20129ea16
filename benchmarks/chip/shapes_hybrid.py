"""Operations that the served prefill of a Granite 4.0-H hybrid requires,
from shapes alone (``shapes.py`` counts the dense decoders).

For one request of L real tokens, with d the hidden size, V the vocabulary,
ff the MLP width, and per Mamba-2 layer H heads of P, d_inner = H·P, state
N (one group), conv width K, chunk Q; per attention layer h query and kv
key/value heads of hd:

- Mamba-2 layer:
  - ``in_proj``: 2·L·d·(2·d_inner + 2N + H);
  - the causal conv: 2·L·K·(d_inner + 2N);
  - the chunked SSD over chunks of Q (the last one holds L mod Q), for each
    chunk of l positions: C·Bᵀ over its l(l+1)/2 causal pairs, 2·N each;
    the masked product with dt·x, 2·H·P per pair; the chunk's state,
    2·l·H·P·N; and, in every chunk after the first, its positions' read of
    the state passed in, 2·l·H·P·N;
  - ``out_proj``: 2·L·d_inner·d.
- Attention layer: q, k, v and o projections, 2·L·d·(h + 2kv)·hd +
  2·L·h·hd·d, and scores and context over the L(L+1)/2 causal pairs,
  2·2·h·hd each.
- Every layer: the SwiGLU MLP, 3·2·L·d·ff.
- The tied head at the last position only: 2·d·V.

Padding is not required work.  Norms, the gate, D·x, the decays and the
residual adds are left out: elementwise, well under 1% of the products.
"""

from __future__ import annotations


def _chunks(length: int, q: int) -> list[int]:
    return [q] * (length // q) + ([length % q] if length % q else [])


def prefill_flops(m: dict, length: int) -> float:
    """FLOPs of one causal prefill of ``length`` real tokens."""
    d, ff, v, L = m["d_model"], m["d_ff"], m["vocab_size"], length
    h, kv = m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or d // h
    H, P, N, K = m["mamba_heads"], m["mamba_head_dim"], m["ssm_state"], m["mamba_conv"]
    di = H * P
    pairs = lambda l: l * (l + 1) // 2  # noqa: E731
    ssd = sum(
        2 * N * pairs(l) + 2 * H * P * pairs(l) + 2 * l * H * P * N * (2 if c else 1)
        for c, l in enumerate(_chunks(L, m["mamba_chunk"]))
    )
    mamba = 2 * L * d * (2 * di + 2 * N + H) + 2 * L * K * (di + 2 * N) + ssd + 2 * L * di * d
    attn = 2 * L * d * (h + 2 * kv) * hd + 2 * L * h * hd * d + 2 * 2 * h * hd * pairs(L)
    mlp = 3 * 2 * L * d * ff
    pattern = m["layer_pattern"]
    kinds = [pattern[i % len(pattern)] for i in range(m["n_layers"])]
    return float(sum((mamba if k == "M" else attn) + mlp for k in kinds) + 2 * d * v)
