"""MoE top-k gating Pallas TPU kernel: fused softmax + iterative top-k.

Row tiles (bt × E) in VMEM.  top_k is small (≤ 4 in the assigned archs), so
an unrolled iterative max (k passes over the row, masking the previous
argmax) beats a full sort and stays vector-unit friendly.  Gates are
renormalised over the selected experts, matching the router semantics of
DBRX/Arctic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import resolve_interpret


def _kernel(logits_ref, gates_ref, idx_ref, *, top_k: int):
    x = logits_ref[...].astype(jnp.float32)  # (bt, E)
    m = jnp.max(x, axis=-1, keepdims=True)
    p = jnp.exp(x - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)

    work = p
    gsum = jnp.zeros((p.shape[0],), jnp.float32)
    gates = []
    idxs = []
    for _ in range(top_k):
        best = jnp.argmax(work, axis=-1)  # (bt,)
        val = jnp.max(work, axis=-1)
        gates.append(val)
        idxs.append(best.astype(jnp.int32))
        gsum = gsum + val
        onehot = (
            jax.lax.broadcasted_iota(jnp.int32, work.shape, 1) == best[:, None]
        )
        work = jnp.where(onehot, -1.0, work)
    g = jnp.stack(gates, axis=-1) / jnp.maximum(gsum, 1e-9)[:, None]
    gates_ref[...] = g.astype(gates_ref.dtype)
    idx_ref[...] = jnp.stack(idxs, axis=-1)


def moe_gating_pallas(
    logits: jax.Array,
    top_k: int,
    *,
    block_rows: int = 256,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """logits: (T, E) → (gates (T, k) f32, idx (T, k) int32)."""
    t, e = logits.shape
    block_rows = min(block_rows, t)
    assert t % block_rows == 0
    return pl.pallas_call(
        functools.partial(_kernel, top_k=top_k),
        grid=(t // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, e), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, top_k), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, top_k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, top_k), jnp.float32),
            jax.ShapeDtypeStruct((t, top_k), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(logits)
