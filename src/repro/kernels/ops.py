"""Public jit'd wrappers for the Pallas kernels.

``use_pallas=True`` runs the Pallas kernel: compiled on a TPU backend, in
the Pallas interpreter on any other (decided when the wrapper is traced,
see :mod:`.backend`); ``False`` runs the pure-jnp oracle — the wrappers
keep signatures identical so the model layer can switch per deployment.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .decode_attention import decode_attention_pallas
from .flash_attention import flash_attention_pallas
from .moe_gating import moe_gating_pallas
from .rmsnorm import rmsnorm_pallas


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "use_pallas", "block_q", "block_k")
)
def flash_attention(
    q,
    k,
    v,
    lengths=None,
    *,
    causal: bool = True,
    window: int = 0,
    use_pallas: bool = True,
    block_q: int = 128,
    block_k: int = 128,
):
    """q: (B, H, S, hd); k, v: (B, KV, S, hd); lengths: (B,) or None."""
    if lengths is None:
        lengths = jnp.full((q.shape[0],), q.shape[2], jnp.int32)
    if not use_pallas:
        return ref.flash_attention_ref(
            q, k, v, causal=causal, lengths=lengths, window=window
        )
    s = q.shape[2]
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    blk = max(block_q, block_k)
    pad = (-s) % blk
    if pad:
        # Pad keys/queries up to the tile size; `lengths` masks padded keys
        # and padded query rows are sliced off below.
        padcfg = ((0, 0), (0, 0), (0, pad), (0, 0))
        q, k, v = jnp.pad(q, padcfg), jnp.pad(k, padcfg), jnp.pad(v, padcfg)
    out = flash_attention_pallas(
        q,
        k,
        v,
        lengths,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
    )
    return out[:, :, :s] if pad else out


@functools.partial(jax.jit, static_argnames=("use_pallas", "block_k"))
def decode_attention(q, k_cache, v_cache, valid_len, *, use_pallas: bool = True, block_k: int = 256):
    """q: (B, H, hd); caches: (B, KV, S, hd); valid_len: (B,)."""
    if not use_pallas:
        return ref.decode_attention_ref(q, k_cache, v_cache, valid_len)
    return decode_attention_pallas(
        q, k_cache, v_cache, valid_len, block_k=block_k
    )


@functools.partial(jax.jit, static_argnames=("use_pallas", "eps"))
def rmsnorm(x, scale, *, eps: float = 1e-6, use_pallas: bool = True):
    """x: (..., d) — flattened to rows internally."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    if not use_pallas:
        out = ref.rmsnorm_ref(x2, scale, eps)
    else:
        out = rmsnorm_pallas(x2, scale, eps=eps)
    return out.reshape(shape)


@functools.partial(jax.jit, static_argnames=("top_k", "use_pallas"))
def moe_gating(logits, top_k: int, *, use_pallas: bool = True):
    """logits: (T, E) → (gates (T,k), idx (T,k))."""
    if not use_pallas:
        return ref.moe_gating_ref(logits, top_k)
    return moe_gating_pallas(logits, top_k)
