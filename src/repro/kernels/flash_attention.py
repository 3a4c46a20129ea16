"""Flash attention (prefill/training) Pallas TPU kernel.

Tiling: grid (B, H, S/bq, S/bk); the (bq × hd) query tile, (bk × hd) K/V
tiles and the f32 accumulator live in VMEM.  Online softmax carries
(m, l, acc) across the innermost k-block dimension — the classic
flash-attention recurrence re-tiled for the MXU (128-aligned tiles).

Per-request ``lengths`` implement the padded-batch execution model the
ORLOJ scheduler reasons about: all requests run at the batch's padded
length (Eq. 3–4), the mask keeps short requests numerically exact.  They
are a scalar-prefetch operand in SMEM, read by ``program_id(0)``.

Supports causal masking, GQA (KV heads < Q heads) and sliding windows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

NEG_INF = -1e30


def _kernel(
    lengths_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    causal: bool,
    window: int,
    sm_scale: float,
    block_q: int,
    block_k: int,
    n_k: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)  # (bk, hd)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale  # (bq, bk)

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = kpos < lengths_ref[pl.program_id(0)]
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[:, 0] = alpha * l_ref[:, 0] + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[:, 0] = m_new

    @pl.when(ki == n_k - 1)
    def _done():
        denom = jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lengths: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """q: (B, H, S, hd); k, v: (B, KV, S, hd); lengths: (B,) int32.

    ``interpret=None`` (default) runs the compiled kernel on a TPU backend
    and the Pallas interpreter elsewhere; True/False forces either mode."""
    b, h, s, hd = q.shape
    kv = k.shape[1]
    assert h % kv == 0
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    n_q, n_k = s // block_q, s // block_k
    qpk = h // kv

    kernel = functools.partial(
        _kernel,
        causal=causal,
        window=window,
        sm_scale=1.0 / np.sqrt(hd),
        block_q=block_q,
        block_k=block_k,
        n_k=n_k,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, n_q, n_k),
            in_specs=[
                pl.BlockSpec(
                    (1, 1, block_q, hd), lambda bi, hi, qi, ki, ln: (bi, hi, qi, 0)
                ),
                pl.BlockSpec(
                    (1, 1, block_k, hd),
                    lambda bi, hi, qi, ki, ln: (bi, hi // qpk, ki, 0),
                ),
                pl.BlockSpec(
                    (1, 1, block_k, hd),
                    lambda bi, hi, qi, ki, ln: (bi, hi // qpk, ki, 0),
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, block_q, hd), lambda bi, hi, qi, ki, ln: (bi, hi, qi, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((block_q, hd), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=resolve_interpret(interpret),
    )(lengths.astype(jnp.int32), q, k, v)
