"""Core neural layers: norms, rotary embeddings, GQA attention, MLPs.

Pure-functional JAX: ``init_*`` builds parameter pytrees (float32 by
default), ``*_apply`` consumes them.  Everything is shape-polymorphic over
batch/sequence and works under pjit with the PartitionSpecs from
:mod:`repro.models.sharding`.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Params = dict[str, Any]

# Weight leaves (by key, in any model here) that the forward only ever uses
# cast: as a matrix-product operand after ``.astype`` to the activations'
# dtype, or cast to the compute dtype outright (the embedding gather, the
# frontend projection).  A chip's default-precision product rounds its
# operands to bfloat16, so holding these leaves in the compute dtype gives
# the same logits there without a cast of every weight on every call
# (``Model.serving_params``).  Norm scales, biases and SSM parameters
# (``a_log``, ``dt_bias``, ``conv``, ...) are used in float32 arithmetic
# and are not listed.
COMPUTE_DTYPE_LEAVES = frozenset(
    {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "table", "lm_head",
     "frontend_proj", "router", "in_proj", "out_proj"}
)


def _init(rng, shape, scale=None, dtype=jnp.float32):
    scale = scale if scale is not None else 1.0 / np.sqrt(shape[0])
    return jax.random.normal(rng, shape, dtype) * scale


# --------------------------------------------------------------- norms
def init_norm(rng, d: int, kind: str) -> Params:
    if kind == "nonparam_ln":
        return {}
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), jnp.float32)}
    if kind == "layernorm":
        return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}
    raise ValueError(kind)


def norm_apply(params: Params, x: jax.Array, kind: str, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
        y = y * params["scale"]
    else:  # layernorm / nonparam_ln
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + eps)
        if kind == "layernorm":
            y = y * params["scale"] + params["bias"]
    return y.astype(dtype)


# ---------------------------------------------------------------- rope
def rope_tables(positions: jax.Array, head_dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """(sin, cos) tables for ``positions`` (any leading shape)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., half)
    return jnp.sin(angles), jnp.cos(angles)


def rope_apply(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """Apply rotary embedding.  x: (..., seq, heads, head_dim); sin/cos
    broadcastable to (..., seq, 1, head_dim/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    sin = sin[..., None, :]
    cos = cos[..., None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(
        x.dtype
    )


# ----------------------------------------------------------- attention
def init_attention(rng, d: int, n_heads: int, n_kv: int, head_dim: int) -> Params:
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    return {
        "wq": _init(k1, (d, n_heads, head_dim)),
        "wk": _init(k2, (d, n_kv, head_dim)),
        "wv": _init(k3, (d, n_kv, head_dim)),
        "wo": _init(k4, (n_heads, head_dim, d), scale=1.0 / np.sqrt(n_heads * head_dim)),
    }


def _gqa_scores(q: jax.Array, k: jax.Array, n_kv: int, scale: float = 0.0) -> jax.Array:
    """q: (B,S,H,hd), k: (B,T,KV,hd) → scores (B, KV, q_per_kv, S, T),
    times ``scale`` (0 → divided by sqrt(hd))."""
    b, s, h, hd = q.shape
    qg = q.reshape(b, s, n_kv, h // n_kv, hd)
    raw = jnp.einsum("bskgh,btkh->bkgst", qg, k)
    return raw * scale if scale else raw / np.sqrt(hd).astype(np.float32)


def attention_apply(
    params: Params,
    x: jax.Array,
    *,
    n_kv: int,
    rope_theta: float,
    sliding_window: int = 0,
    positions: jax.Array | None = None,
    softcap: float = 0.0,
    repeat_kv: bool = False,
    rope: bool = True,
    scale: float = 0.0,
) -> jax.Array:
    """Full (training / prefill) causal GQA attention.  x: (B, S, d).
    ``rope=False``: no positions at all (NoPE); ``scale``: the scores'
    multiplier (0 → 1/sqrt(head_dim)).

    ``repeat_kv=True`` broadcasts K/V to the full head count before the
    score einsums: all attention tensors are then (B, S, H, ·) and shard
    cleanly on the head axis (the (kv, group) reshape of the baseline
    formulation forces GSPMD reshards when kv ∤ mesh_model)."""
    b, s, _ = x.shape
    dtype = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dtype))
    if rope:
        if positions is None:
            positions = jnp.arange(s)[None, :]
        sin, cos = rope_tables(positions, q.shape[-1], rope_theta)
        q = rope_apply(q, sin, cos)
        k = rope_apply(k, sin, cos)
    h = q.shape[2]

    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    mask = j <= i
    if sliding_window > 0:
        mask &= j > i - sliding_window

    if repeat_kv:
        rep = h // n_kv
        k = jnp.repeat(k, rep, axis=2)  # (B, S, H, hd)
        v = jnp.repeat(v, rep, axis=2)
        scores = jnp.einsum("bshk,bthk->bhst", q, k).astype(jnp.float32)
        scores = scores * scale if scale else scores / np.sqrt(q.shape[-1]).astype(np.float32)
        if softcap > 0:
            scores = jnp.tanh(scores / softcap) * softcap
        scores = jnp.where(mask[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        ctx = jnp.einsum("bhst,bthk->bshk", probs, v)
        return jnp.einsum("bshk,hkd->bsd", ctx, params["wo"].astype(dtype))

    scores = _gqa_scores(q, k, n_kv, scale).astype(jnp.float32)
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    ctx = jnp.einsum("bkgst,btkh->bskgh", probs, v).reshape(b, s, h, -1)
    return jnp.einsum("bshk,hkd->bsd", ctx, params["wo"].astype(dtype))


def init_kv_cache(
    batch: int, n_kv: int, cache_len: int, head_dim: int, dtype=jnp.bfloat16
) -> Params:
    return {
        "k": jnp.zeros((batch, cache_len, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, cache_len, n_kv, head_dim), dtype),
    }


def attention_decode(
    params: Params,
    x: jax.Array,
    cache: Params,
    pos: jax.Array,
    *,
    n_kv: int,
    rope_theta: float,
    sliding_window: int = 0,
    softcap: float = 0.0,
    rope: bool = True,
    scale: float = 0.0,
) -> tuple[jax.Array, Params]:
    """One-token decode with a KV cache.  x: (B, 1, d); ``pos`` scalar int;
    ``rope`` and ``scale`` as in :func:`attention_apply`.

    With ``sliding_window > 0`` the cache is a ring buffer of length W
    (positions are absolute for RoPE; the slot is ``pos mod W``) — this is
    the sub-quadratic/sub-linear long-context variant.
    """
    b, one, _ = x.shape
    dtype = x.dtype
    cache_len = cache["k"].shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dtype))
    if rope:
        posv = jnp.full((b, 1), pos)
        sin, cos = rope_tables(posv, q.shape[-1], rope_theta)
        q = rope_apply(q, sin, cos)
        k = rope_apply(k, sin, cos)

    slot = jnp.where(sliding_window > 0, pos % cache_len, pos)
    new_k = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
    new_v = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))

    scores = _gqa_scores(q, new_k.astype(dtype), n_kv, scale).astype(jnp.float32)
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    idx = jnp.arange(cache_len)
    valid = idx <= jnp.minimum(pos, cache_len - 1) if sliding_window == 0 else (
        idx < jnp.minimum(pos + 1, cache_len)
    )
    scores = jnp.where(valid[None, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    h = q.shape[2]
    ctx = jnp.einsum("bkgst,btkh->bskgh", probs, new_v.astype(dtype)).reshape(b, one, h, -1)
    out = jnp.einsum("bshk,hkd->bsd", ctx, params["wo"].astype(dtype))
    return out, {"k": new_k, "v": new_v}


# ------------------------------------------------------------------ mlp
def init_mlp(rng, d: int, ff: int, kind: str) -> Params:
    if kind == "none":
        return {}
    ks = jax.random.split(rng, 3)
    if kind == "swiglu":
        return {
            "w_gate": _init(ks[0], (d, ff)),
            "w_up": _init(ks[1], (d, ff)),
            "w_down": _init(ks[2], (ff, d), scale=1.0 / np.sqrt(ff)),
        }
    return {
        "w_up": _init(ks[0], (d, ff)),
        "w_down": _init(ks[1], (ff, d), scale=1.0 / np.sqrt(ff)),
    }


def mlp_apply(params: Params, x: jax.Array, kind: str) -> jax.Array:
    dtype = x.dtype
    if kind == "none":
        return jnp.zeros_like(x)
    if kind == "swiglu":
        g = x @ params["w_gate"].astype(dtype)
        u = x @ params["w_up"].astype(dtype)
        return (jax.nn.silu(g) * u) @ params["w_down"].astype(dtype)
    u = x @ params["w_up"].astype(dtype)
    if kind == "gelu":
        u = jax.nn.gelu(u)
    elif kind == "relu2":  # Nemotron-4 squared ReLU
        u = jnp.square(jax.nn.relu(u))
    else:
        raise ValueError(kind)
    return u @ params["w_down"].astype(dtype)


# ------------------------------------------------------------ embedding
def init_embedding(rng, vocab: int, d: int) -> Params:
    return {"table": _init(rng, (vocab, d), scale=1.0)}


def embed_apply(params: Params, tokens: jax.Array, dtype) -> jax.Array:
    return params["table"].astype(dtype)[tokens]


def unembed_apply(params: Params, x: jax.Array) -> jax.Array:
    return jnp.einsum("bsd,vd->bsv", x, params["table"].astype(x.dtype))
