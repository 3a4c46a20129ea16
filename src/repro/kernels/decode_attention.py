"""GQA flash-decode Pallas TPU kernel: one query token vs. a long KV cache.

Tiling: grid (B, KV, S/bk).  For each KV head, the q-group tile
(q_per_kv × hd) stays resident in VMEM while K/V cache tiles (bk × hd)
stream through; (m, l, acc) carry the online softmax across cache blocks —
flash-decoding adapted to the TPU memory hierarchy (the cache streams
HBM→VMEM; the group matmul feeds the MXU).

``valid_len`` masks unwritten cache slots (the serving engine's ring
buffer / partially-filled cache).  It is a scalar-prefetch operand: it sits
in SMEM and each grid step reads its row's length by ``program_id(0)``,
since a ``(1, 1)`` VMEM block of a ``(B, 1)`` array breaks the TPU's
(8, 128) block-tiling rule for every B > 1.

Cache lengths need not be multiples of ``block_k``: the cache is padded up
to one, and ``valid_len`` masks the padding.  Every K/V tile is then
``block_k`` rows, a multiple of 8 as the TPU's tiling requires.  The pad is
a copy of the cache, so keep caches multiples of ``block_k``.
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
    valid_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    sm_scale: float,
    block_k: int,
    n_k: int,
):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (g, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale  # (g, bk)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < valid_ref[pl.program_id(0)]
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
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


def decode_attention_pallas(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    valid_len: jax.Array,
    *,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """q: (B, H, hd); k/v_cache: (B, KV, S, hd); valid_len: (B,) int32.

    ``interpret=None`` (default) runs the compiled kernel on a TPU backend
    and the Pallas interpreter elsewhere; True/False forces either mode."""
    b, h, hd = q.shape
    kv, s = k_cache.shape[1], k_cache.shape[2]
    assert h % kv == 0
    g = h // kv
    if s <= 0:
        raise ValueError(f"cache length must be positive, got S={s}")
    block_k = min(block_k, -(-s // 8) * 8)
    if block_k % 8:
        raise ValueError(f"block_k must be a multiple of 8, got {block_k}")
    pad = (-s) % block_k
    if pad:
        padcfg = ((0, 0), (0, 0), (0, pad), (0, 0))
        k_cache, v_cache = jnp.pad(k_cache, padcfg), jnp.pad(v_cache, padcfg)
    n_k = (s + pad) // block_k
    qg = q.reshape(b, kv, g, hd)

    kernel = functools.partial(
        _kernel, sm_scale=1.0 / np.sqrt(hd), block_k=block_k, n_k=n_k
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kv, n_k),
            in_specs=[
                pl.BlockSpec((1, 1, g, hd), lambda bi, ci, ki, vl: (bi, ci, 0, 0)),
                pl.BlockSpec(
                    (1, 1, block_k, hd), lambda bi, ci, ki, vl: (bi, ci, ki, 0)
                ),
                pl.BlockSpec(
                    (1, 1, block_k, hd), lambda bi, ci, ki, vl: (bi, ci, ki, 0)
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, g, hd), lambda bi, ci, ki, vl: (bi, ci, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((g, hd), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(valid_len.astype(jnp.int32), qg, k_cache, v_cache)
    return out.reshape(b, h, hd)
