"""Plain reference of a Granite 4.0-H hybrid decoder (``granitemoehybrid``
with no experts), and its weights.

Layers follow one period of kinds, repeated over the depth: ``M`` a Mamba-2
mixer, ``A`` causal GQA attention without positions (NoPE), scores times
``attention_multiplier``.  Every layer is pre-norm (RMSNorm), its mixer's
output times ``residual_multiplier`` added to the residual, then a SwiGLU
MLP the same way.  The token embedding is times ``embedding_multiplier``;
the final RMSNorm, the tied head, then the logits over ``logits_scaling``.

The Mamba-2 mixer, as the published Mamba-2 code defines it, with one group:
``in_proj`` gives z (d_inner), xBC (d_inner + 2N) and dt (one per head);
a depthwise causal conv of width ``mamba_conv`` with bias over xBC, SiLU;
``dt = softplus(dt + dt_bias)``, ``A = -exp(a_log)``; the scan is the plain
recurrence, one position at a time:
``h_t = exp(dt_t A) h_{t-1} + dt_t x_t ⊗ B_t``, ``y_t = h_t · C_t + D x_t``;
then RMSNorm over d_inner of ``y · silu(z)`` times its scale, ``out_proj``.

It imports nothing of the program.  The weights are made here, from the
seed, in the layout the program's ``Model`` takes (a list of one dict per
layer of the period, each leaf stacked over the periods), and in the
configuration's ``param_dtype``, so the same arrays feed both.  The forward
casts one layer's weights to float32 at a time, inside its loop over the
layers.  Every matrix product is float32 at ``Precision.HIGHEST``.  The
control (``precision="fp8"``) rounds both operands of every product to
float8 e4m3 first: the step below the bfloat16 the configuration states.

Departures from the published model: none in the arithmetic.  The weights
are random: the embedding has standard deviation 1 / ``embedding_multiplier``
(the stream starts at unit scale), A = -U(1, 16) per head and dt's bias is
softplus⁻¹ of a log-uniform draw in [1e-3, 1e-1] (Mamba-2's initialisation),
norm scales and D near 1.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _sizes(m: dict) -> dict:
    d, h, kv = m["d_model"], m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or d // h
    mh, mp, n = m["mamba_heads"], m["mamba_head_dim"], m["ssm_state"]
    return dict(d=d, h=h, kv=kv, hd=hd, ff=m["d_ff"], v=m["vocab_size"], mh=mh, mp=mp, n=n,
                di=mh * mp, k=m["mamba_conv"], units=m["n_layers"] // len(m["layer_pattern"]))


def _shapes(m: dict) -> dict:
    z = _sizes(m)
    d, u, di, n = z["d"], z["units"], z["di"], z["n"]
    norm = {"scale": (u, d)}
    mlp = {"w_gate": (u, d, z["ff"]), "w_up": (u, d, z["ff"]), "w_down": (u, z["ff"], d)}
    mamba = {
        "in_proj": (u, d, 2 * di + 2 * n + z["mh"]),
        "conv_w": (u, z["k"], di + 2 * n),
        "conv_b": (u, di + 2 * n),
        "dt_bias": (u, z["mh"]),
        "a_log": (u, z["mh"]),
        "d_skip": (u, z["mh"]),
        "norm_scale": (u, di),
        "out_proj": (u, di, d),
    }
    attn = {"wq": (u, d, z["h"], z["hd"]), "wk": (u, d, z["kv"], z["hd"]),
            "wv": (u, d, z["kv"], z["hd"]), "wo": (u, z["h"], z["hd"], d)}
    mixers = {"M": {"mamba2": mamba}, "A": {"attn": attn}}
    blocks = [{"norm1": norm, **mixers[k], "norm2": norm, "mlp": mlp} for k in m["layer_pattern"]]
    return {"embed": {"table": (z["v"], d)}, "blocks": blocks, "final_norm": {"scale": (d,)}}


def _draw(key, path: tuple, shape: tuple, m: dict) -> jax.Array:
    """One leaf, float32: unit-scale signals through every product."""
    name = path[-1]
    normal = jax.random.normal(key, shape, jnp.float32)
    if name == "table":
        return normal / m["embedding_multiplier"]
    if name in ("scale", "norm_scale", "d_skip"):
        return 1.0 + 0.1 * normal
    if name == "conv_b":
        return 0.1 * normal
    if name == "conv_w":
        return normal / np.sqrt(shape[-2])
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "wo":
        return normal / np.sqrt(shape[-3] * shape[-2])
    fan_in = shape[-3] if name in ("wq", "wk", "wv") else shape[-2]
    return normal / np.sqrt(fan_in)


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
    """All weights in one jitted call on the default device, in
    ``param_dtype``, from a 32-bit seed."""
    shapes = _shapes(model)
    dtype = jnp.dtype(model.get("param_dtype", "float32"))

    @jax.jit
    def build(s):
        key = jax.random.key(s)
        out = jax.tree.map(lambda x: x, shapes, is_leaf=lambda x: isinstance(x, tuple))
        for i, (path, shape) in enumerate(_leaves(shapes)):
            _set(out, path, _draw(jax.random.fold_in(key, i), path, shape, model).astype(dtype))
        return out

    return build(jnp.uint32(seed32))


def _rms(x, scale, eps=1e-5):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _q8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def make_forward(model: dict, precision: str = "f32"):
    """``fwd(weights, tokens)`` -> logits (S, vocab) float32 for one
    sequence ``tokens`` (S,); jitted, so one program per length."""
    m = model
    z = _sizes(m)
    cast = _q8 if precision == "fp8" else (lambda x: x)
    di, n, mh, mp = z["di"], z["n"], z["mh"], z["mp"]
    rm = m["residual_multiplier"]

    def ein(spec, a, b):
        return jnp.einsum(spec, cast(a), cast(b), precision=HI)

    def mamba2(x, p):
        s = x.shape[0]
        zg, xbc, dt = jnp.split(ein("sd,de->se", x, p["in_proj"]), [di, 2 * di + 2 * n], axis=-1)
        k = p["conv_w"].shape[0]
        xp = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), xbc.dtype), xbc])
        conv = sum(xp[i : i + s] * p["conv_w"][i] for i in range(k)) + p["conv_b"]
        xs, bm, cm = jnp.split(jax.nn.silu(conv), [di, di + n], axis=-1)
        xs = xs.reshape(s, mh, mp)
        dt = jax.nn.softplus(dt + p["dt_bias"])  # (S, H)
        a = -jnp.exp(p["a_log"])

        def step(h, inp):
            x_t, dt_t, b_t, c_t = inp
            h = jnp.exp(dt_t * a)[:, None, None] * h + (dt_t[:, None] * x_t)[..., None] * b_t
            return h, ein("hpn,n->hp", h, c_t)

        _, y = jax.lax.scan(step, jnp.zeros((mh, mp, n), jnp.float32), (xs, dt, bm, cm))
        y = (y + p["d_skip"][:, None] * xs).reshape(s, di)
        g = y * jax.nn.silu(zg)
        return ein("se,ed->sd", _rms(g, p["norm_scale"]), p["out_proj"])

    def attention(x, p):
        s = x.shape[0]
        q = ein("sd,dhk->shk", x, p["wq"])
        k = ein("sd,dhk->shk", x, p["wk"])
        v = ein("sd,dhk->shk", x, p["wv"])
        g = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
        sc = ein("shk,thk->hst", q, k) * m["attention_multiplier"]
        causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        pr = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        return ein("shk,hkd->sd", ein("hst,thk->shk", pr, v), p["wo"])

    def period(x, unit):
        for kind, p in zip(m["layer_pattern"], unit):
            p = jax.tree.map(lambda w: w.astype(jnp.float32), p)  # this layer only
            h = _rms(x, p["norm1"]["scale"])
            x = x + rm * (mamba2(h, p["mamba2"]) if kind == "M" else attention(h, p["attn"]))
            h2 = _rms(x, p["norm2"]["scale"])
            w = p["mlp"]
            u = jax.nn.silu(ein("sd,df->sf", h2, w["w_gate"])) * ein("sd,df->sf", h2, w["w_up"])
            x = x + rm * ein("sf,fd->sd", u, w["w_down"])
        return x, None

    @jax.jit
    def fwd(weights, tokens):
        table = weights["embed"]["table"]
        x = table[tokens].astype(jnp.float32) * m["embedding_multiplier"]
        x, _ = jax.lax.scan(period, x, weights["blocks"])
        x = _rms(x, weights["final_norm"]["scale"].astype(jnp.float32))
        return ein("sd,vd->sv", x, table.astype(jnp.float32)) / m["logits_scaling"]

    return fwd
