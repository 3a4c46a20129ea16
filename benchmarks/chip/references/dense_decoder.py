"""Plain reference of a dense decoder-only transformer, and its weights.

Pre-norm blocks (attention then MLP, each added to the residual), rotary
positions on q and k, causal softmax attention with one query head per
group of ``n_heads / n_kv_heads``, token embedding scaled by sqrt(d_model),
final norm, then the LM head (or the transposed embedding when tied).
Norms: ``layernorm`` (learned scale and bias), ``nonparam_ln`` (OLMo: no
parameters), ``rmsnorm``.  MLPs: ``gelu`` (tanh approximation), ``swiglu``.

It imports nothing of the program.  The weights are made here, from the
seed, in the layout the program's ``Model`` takes (a dict per layer kind,
layers stacked on a leading axis), so the same arrays feed both.

Every matrix product is float32 at ``Precision.HIGHEST``.  The control
(``precision="fp8"``) rounds both operands of every product to float8 e4m3
first: the step below the bfloat16 the configurations state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _shapes(m: dict) -> dict:
    d, h, kv, ff, v, n = (m[k] for k in ("d_model", "n_heads", "n_kv_heads", "d_ff", "vocab_size", "n_layers"))
    hd = m.get("head_dim") or d // h
    norm = {"layernorm": {"scale": (n, d), "bias": (n, d)}, "rmsnorm": {"scale": (n, d)}, "nonparam_ln": {}}[m["norm"]]
    mlp = {"w_up": (n, d, ff), "w_down": (n, ff, d)}
    if m["mlp"] == "swiglu":
        mlp["w_gate"] = (n, d, ff)
    block = {
        "norm1": norm,
        "attn": {"wq": (n, d, h, hd), "wk": (n, d, kv, hd), "wv": (n, d, kv, hd), "wo": (n, h, hd, d)},
        "norm2": norm,
        "mlp": mlp,
    }
    final = {k: s[1:] for k, s in norm.items()}
    out = {"embed": {"table": (v, d)}, "blocks": [block], "final_norm": final}
    if not m.get("tie_embeddings"):
        out["lm_head"] = (d, v)
    return out


def _scale(path: tuple, shape: tuple) -> tuple[float, float]:
    """(mean, std) of a leaf: unit-variance signals through every product,
    as a trained model keeps them; norms near 1 with a visible bias."""
    name = path[-1]
    if name == "table":
        return 0.0, 1.0
    if name == "scale":
        return 1.0, 0.1
    if name == "bias":
        return 0.0, 0.1
    if name == "wo":
        return 0.0, 1.0 / np.sqrt(shape[-3] * shape[-2])
    fan_in = shape[-2] if name in ("w_up", "w_gate", "w_down", "lm_head") else shape[-3]
    return 0.0, 1.0 / np.sqrt(fan_in)


def _leaves(tree, path=()):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaves(tree[k], path + (k,))
    elif isinstance(tree, list):
        for i, t in enumerate(tree):
            yield from _leaves(t, path + (i,))
    else:
        yield path, tree


def _set(tree, path, value):
    for p in path[:-1]:
        tree = tree[p]
    tree[path[-1]] = value


def make_weights(model: dict, seed32: int):
    """All weights in one jitted call on the default device, float32 (the
    type the program serves them in), from a 32-bit seed."""
    shapes = _shapes(model)

    @jax.jit
    def build(s):
        key = jax.random.key(s)
        out = jax.tree.map(lambda x: x, shapes, is_leaf=lambda x: isinstance(x, tuple))
        for i, (path, shape) in enumerate(_leaves(shapes)):
            mean, std = _scale(path, shape)
            x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            _set(out, path, mean + std * x)
        return out

    return build(jnp.uint32(seed32))


def _norm(x, p, kind, eps=1e-5):
    if kind == "rmsnorm":
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + eps)
    return y * p["scale"] + p["bias"] if kind == "layernorm" else y


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs  # (S, half)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _q8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def make_forward(model: dict, precision: str = "f32"):
    """``fwd(weights, tokens)`` -> logits (S, vocab) float32 for one
    sequence ``tokens`` (S,); jitted, so one program per length."""
    m = model
    cast = _q8 if precision == "fp8" else (lambda x: x)
    kind, n_kv, theta = m["norm"], m["n_kv_heads"], m.get("rope_theta", 10000.0)

    def ein(spec, a, b):
        return jnp.einsum(spec, cast(a), cast(b), precision=HI)

    def layer(x, p):
        s = x.shape[0]
        h = _norm(x, p["norm1"], kind)
        a = p["attn"]
        q = _rope(ein("sd,dhk->shk", h, a["wq"]), jnp.arange(s), theta)
        k = _rope(ein("sd,dhk->shk", h, a["wk"]), jnp.arange(s), theta)
        v = ein("sd,dhk->shk", h, a["wv"])
        g = q.shape[1] // n_kv
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
        sc = ein("shk,thk->hst", q, k) / np.sqrt(q.shape[-1])
        causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        sc = jnp.where(causal[None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        ctx = ein("hst,thk->shk", pr, v)
        x = x + ein("shk,hkd->sd", ctx, a["wo"])
        h2 = _norm(x, p["norm2"], kind)
        w = p["mlp"]
        u = ein("sd,df->sf", h2, w["w_up"])
        if m["mlp"] == "swiglu":
            u = jax.nn.silu(ein("sd,df->sf", h2, w["w_gate"])) * u
        else:
            u = jax.nn.gelu(u, approximate=True)
        return x + ein("sf,fd->sd", u, w["w_down"]), None

    @jax.jit
    def fwd(weights, tokens):
        table = weights["embed"]["table"]
        x = table[tokens] * np.sqrt(m["d_model"]).astype(np.float32)
        x, _ = jax.lax.scan(layer, x, weights["blocks"][0])
        x = _norm(x, weights["final_norm"], kind)
        if m.get("tie_embeddings"):
            return ein("sd,vd->sv", x, table)
        return ein("sd,dv->sv", x, weights["lm_head"])

    return fwd
