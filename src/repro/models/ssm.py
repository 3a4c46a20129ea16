"""State-space and recurrent cells: Mamba (Hymba's parallel-SSM head),
Mamba-2 (Granite 4.0-H's mixer, chunked SSD), mLSTM and sLSTM (xLSTM
blocks).

TPU adaptation notes (see DESIGN.md): the CUDA selective-scan kernel of
Mamba and the fused mLSTM kernels are re-expressed as *chunkwise-parallel*
computations — within a chunk we use ``jax.lax.associative_scan`` (Mamba)
or dense intra-chunk matmuls (mLSTM, MXU-friendly), and chunks are combined
with a short, unrolled sequential carry.  This keeps the HLO free of
while-loops for the scan-heavy paths (so ``cost_analysis`` FLOPs are
meaningful) and maps the recurrence onto the systolic units instead of
emulating warp-level CUDA tricks.  The sLSTM recurrence is inherently
sequential (gate recurrence on h_{t-1}); it uses ``lax.scan`` and we account
for its trip count explicitly in the roofline.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .layers import _init

Params = dict[str, Any]


def _chunked(x: jax.Array, chunk: int) -> tuple[jax.Array, int]:
    s = x.shape[1]
    chunk = min(chunk, s)
    if s % chunk:
        # round chunk down to a divisor of s
        while s % chunk:
            chunk -= 1
    return x, chunk


# =====================================================================
# Mamba (selective SSM) — Hymba's parallel SSM head
# =====================================================================
def init_mamba(rng, d: int, n_state: int, dt_rank: int = 16, conv_w: int = 4) -> Params:
    ks = jax.random.split(rng, 8)
    return {
        "in_x": _init(ks[0], (d, d)),
        "in_z": _init(ks[1], (d, d)),
        "conv": _init(ks[2], (conv_w, d), scale=1.0 / np.sqrt(conv_w)),
        "w_b": _init(ks[3], (d, n_state)),
        "w_c": _init(ks[4], (d, n_state)),
        "w_dt_lo": _init(ks[5], (d, dt_rank)),
        "w_dt_hi": _init(ks[6], (dt_rank, d)),
        "dt_bias": jnp.zeros((d,), jnp.float32),
        "a_log": jnp.log(jnp.arange(1, n_state + 1, dtype=jnp.float32))[None, :]
        * jnp.ones((d, 1), jnp.float32),
        "d_skip": jnp.ones((d,), jnp.float32),
        "out": _init(ks[7], (d, d)),
    }


def _mamba_scan(a: jax.Array, b: jax.Array, h0: jax.Array, chunk: int):
    """h_t = a_t · h_{t-1} + b_t, chunkwise-parallel.

    a, b: (B, S, d, N); h0: (B, d, N).  Returns (h_all (B,S,d,N), h_last).
    """
    _, chunk = _chunked(a, chunk)
    bsz, s, d, n = a.shape
    nc = s // chunk
    a_c = a.reshape(bsz, nc, chunk, d, n)
    b_c = b.reshape(bsz, nc, chunk, d, n)

    def combine(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, b1 * a2 + b2

    outs = []
    h = h0
    for c in range(nc):  # unrolled short carry (≤ 32 iterations)
        acum, bcum = jax.lax.associative_scan(
            combine, (a_c[:, c], b_c[:, c]), axis=1
        )
        h_t = acum * h[:, None] + bcum  # (B, chunk, d, N)
        outs.append(h_t)
        h = h_t[:, -1]
    return jnp.concatenate(outs, axis=1), h


def mamba_apply(
    params: Params, x: jax.Array, chunk: int = 256
) -> jax.Array:
    """x: (B, S, d) → (B, S, d)."""
    dtype = x.dtype
    xb = x @ params["in_x"].astype(dtype)
    z = x @ params["in_z"].astype(dtype)
    # causal depthwise conv, window w
    w = params["conv"].shape[0]
    pad = jnp.pad(xb, ((0, 0), (w - 1, 0), (0, 0)))
    xc = sum(
        pad[:, i : i + x.shape[1]] * params["conv"][i].astype(dtype)
        for i in range(w)
    )
    xc = jax.nn.silu(xc)

    dt = jax.nn.softplus(
        (xc @ params["w_dt_lo"].astype(dtype)) @ params["w_dt_hi"].astype(dtype)
        + params["dt_bias"].astype(dtype)
    )  # (B,S,d)
    a = jnp.exp(
        -jnp.exp(params["a_log"].astype(jnp.float32))[None, None] * dt[..., None].astype(jnp.float32)
    )  # (B,S,d,N)
    bmat = xc @ params["w_b"].astype(dtype)  # (B,S,N)
    cmat = xc @ params["w_c"].astype(dtype)  # (B,S,N)
    bterm = (dt * xc)[..., None] * bmat[:, :, None, :]  # (B,S,d,N)

    h0 = jnp.zeros((x.shape[0], x.shape[2], bmat.shape[-1]), a.dtype)
    h_all, _ = _mamba_scan(a, bterm.astype(a.dtype), h0, chunk)
    y = jnp.einsum("bsdn,bsn->bsd", h_all.astype(dtype), cmat)
    y = y + xc * params["d_skip"].astype(dtype)
    y = y * jax.nn.silu(z)
    return y @ params["out"].astype(dtype)


def init_mamba_cache(batch: int, d: int, n_state: int, conv_w: int = 4, dtype=jnp.float32) -> Params:
    return {
        "h": jnp.zeros((batch, d, n_state), dtype),
        "conv": jnp.zeros((batch, conv_w - 1, d), dtype),
    }


def mamba_decode(
    params: Params, x: jax.Array, cache: Params
) -> tuple[jax.Array, Params]:
    """One-step decode.  x: (B, 1, d)."""
    dtype = x.dtype
    xb = x[:, 0] @ params["in_x"].astype(dtype)  # (B, d)
    z = x[:, 0] @ params["in_z"].astype(dtype)
    w = params["conv"].shape[0]
    hist = jnp.concatenate([cache["conv"].astype(dtype), xb[:, None]], axis=1)  # (B,w,d)
    xc = jnp.einsum("bwd,wd->bd", hist, params["conv"].astype(dtype))
    xc = jax.nn.silu(xc)
    dt = jax.nn.softplus(
        (xc @ params["w_dt_lo"].astype(dtype)) @ params["w_dt_hi"].astype(dtype)
        + params["dt_bias"].astype(dtype)
    )
    a = jnp.exp(
        -jnp.exp(params["a_log"].astype(jnp.float32))[None] * dt[..., None].astype(jnp.float32)
    )  # (B,d,N)
    bmat = xc @ params["w_b"].astype(dtype)
    cmat = xc @ params["w_c"].astype(dtype)
    h = a * cache["h"].astype(a.dtype) + ((dt * xc)[..., None] * bmat[:, None, :]).astype(a.dtype)
    y = jnp.einsum("bdn,bn->bd", h.astype(dtype), cmat) + xc * params["d_skip"].astype(dtype)
    y = y * jax.nn.silu(z)
    out = (y @ params["out"].astype(dtype))[:, None]
    new_cache = {"h": h.astype(cache["h"].dtype), "conv": hist[:, 1:].astype(cache["conv"].dtype)}
    return out, new_cache


# =====================================================================
# Mamba-2 (SSD) — Granite 4.0-H's mixer
# =====================================================================
def init_mamba2(rng, d: int, n_heads: int, head_dim: int, n_state: int, conv_w: int = 4) -> Params:
    """One group (B and C shared by every head).  ``in_proj`` gives, in this
    order, the gate z (di), the conv stream xBC (di + 2N) and dt (heads);
    dt's bias is softplus⁻¹ of a log-uniform draw in [1e-3, 1e-1] and
    A = -(1..heads), as the published Mamba-2 initialises them."""
    di = n_heads * head_dim
    ks = jax.random.split(rng, 4)
    dt = jnp.exp(jax.random.uniform(ks[2], (n_heads,), jnp.float32, np.log(1e-3), np.log(1e-1)))
    return {
        "in_proj": _init(ks[0], (d, 2 * di + 2 * n_state + n_heads)),
        "conv_w": _init(ks[1], (conv_w, di + 2 * n_state), scale=1.0 / np.sqrt(conv_w)),
        "conv_b": jnp.zeros((di + 2 * n_state,), jnp.float32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "a_log": jnp.log(jnp.arange(1, n_heads + 1, dtype=jnp.float32)),
        "d_skip": jnp.ones((n_heads,), jnp.float32),
        "norm_scale": jnp.ones((di,), jnp.float32),
        "out_proj": _init(ks[3], (di, d)),
    }


def _causal_conv(x: jax.Array, w: jax.Array, bias: jax.Array) -> jax.Array:
    """Depthwise causal conv of width ``w.shape[0]`` over the sequence, then
    SiLU; summed in float32, returned in ``x``'s dtype.  x: (B, S, C)."""
    k, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(xp[:, i : i + s] * w[i].astype(jnp.float32) for i in range(k))
    return jax.nn.silu(y + bias.astype(jnp.float32)).astype(x.dtype)


def _gated_rmsnorm(y: jax.Array, z: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm over the whole inner width of y · silu(z), in float32."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g * scale.astype(jnp.float32)).astype(z.dtype)


def ssd(
    x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array, cm: jax.Array, chunk: int
) -> jax.Array:
    """The Mamba-2 scan h_t = exp(dt_t a) h_{t-1} + dt_t x_t ⊗ B_t,
    y_t = h_t · C_t, in its chunked (state-space duality) form.

    x: (B, S, H, P) in the compute dtype; dt: (B, S, H) float32; a: (H,)
    float32 (negative); bm, cm: (B, S, N).  Returns y (B, S, H, P) float32.
    Chunks hold ``min(chunk, S)`` positions (S is padded at the right to a
    whole number of them; padding never reaches an earlier position).
    Within a chunk y is a masked product C Bᵀ ∘ decay with dt·x; each chunk's
    final state is passed to the next by a scan over chunks.  The decays
    (dt·a, their cumulative sums, exp) and the passed states are float32;
    the products' operands are in x's dtype."""
    f32, dtype = jnp.float32, x.dtype
    b, s, h, p = x.shape
    n = bm.shape[-1]
    q = min(chunk, s)
    pad = -s % q
    if pad:
        x, dt, bm, cm = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) for t in (x, dt, bm, cm)
        )
    nc = (s + pad) // q
    x = x.reshape(b, nc, q, h, p)
    dt = dt.reshape(b, nc, q, h)
    bm = bm.reshape(b, nc, q, n)
    cm = cm.reshape(b, nc, q, n)

    cum = jnp.cumsum(dt * a, axis=2).transpose(0, 1, 3, 2)  # (b, c, h, q), ≤ 0, falling
    causal = jnp.tril(jnp.ones((q, q), bool))
    seg = jnp.where(causal, cum[..., :, None] - cum[..., None, :], -jnp.inf)
    xdt = (x.astype(f32) * dt[..., None]).astype(dtype)
    g = jnp.einsum("bcin,bcjn->bcij", cm, bm)
    m = (g[:, :, None].astype(f32) * jnp.exp(seg)).astype(dtype)  # (b, c, h, i, j)
    y = jnp.einsum("bchij,bcjhp->bcihp", m, xdt, preferred_element_type=f32)

    to_end = jnp.exp(cum[..., -1:] - cum).transpose(0, 1, 3, 2)  # (b, c, q, h)
    xw = (x.astype(f32) * (dt * to_end)[..., None]).astype(dtype)
    states = jnp.einsum("bcjhp,bcjn->bchpn", xw, bm, preferred_element_type=f32)

    def carry(h_in, inp):
        st, decay = inp
        return h_in * decay[..., None, None] + st, h_in

    _, entering = jax.lax.scan(
        carry,
        jnp.zeros((b, h, p, n), f32),
        (states.swapaxes(0, 1), jnp.exp(cum[..., -1]).swapaxes(0, 1)),
    )
    y_off = jnp.einsum(
        "bcin,cbhpn->bcihp", cm, entering.astype(dtype), preferred_element_type=f32
    ) * jnp.exp(cum).transpose(0, 1, 3, 2)[..., None]
    return (y + y_off).reshape(b, nc * q, h, p)[:, :s]


def mamba2_apply(
    params: Params, x: jax.Array, *, n_heads: int, head_dim: int, n_state: int, chunk: int,
    eps: float = 1e-5,
) -> jax.Array:
    """Mamba-2 mixer, x: (B, S, d) → (B, S, d): in_proj → (z, xBC, dt);
    causal conv + SiLU over xBC; dt = softplus(dt + dt_bias), A = -exp(a_log);
    y = SSD(x, dt, A, B, C) + D·x; gated RMSNorm with z; out_proj."""
    b, s, _ = x.shape
    dtype = x.dtype
    di = n_heads * head_dim
    with jax.named_scope("orloj.mamba2.in"):
        zxbcdt = x @ params["in_proj"].astype(dtype)
        z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * n_state], axis=-1)
    with jax.named_scope("orloj.mamba2.conv"):
        xs, bm, cm = jnp.split(
            _causal_conv(xbc, params["conv_w"], params["conv_b"]), [di, di + n_state], axis=-1
        )
        xs = xs.reshape(b, s, n_heads, head_dim)
    with jax.named_scope("orloj.mamba2.ssd"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(params["a_log"].astype(jnp.float32))
        y = ssd(xs, dt, a, bm, cm, chunk)
        y = y + params["d_skip"].astype(jnp.float32)[:, None] * xs.astype(jnp.float32)
    with jax.named_scope("orloj.mamba2.norm"):
        y = _gated_rmsnorm(y.reshape(b, s, di), z, params["norm_scale"], eps)
    with jax.named_scope("orloj.mamba2.out"):
        return y @ params["out_proj"].astype(dtype)


def init_mamba2_cache(
    batch: int, n_heads: int, head_dim: int, n_state: int, conv_w: int = 4, dtype=jnp.float32
) -> Params:
    """The conv window's last ``conv_w - 1`` inputs and the float32 state."""
    return {
        "conv": jnp.zeros((batch, conv_w - 1, n_heads * head_dim + 2 * n_state), dtype),
        "ssm": jnp.zeros((batch, n_heads, head_dim, n_state), jnp.float32),
    }


def mamba2_decode(
    params: Params, x: jax.Array, cache: Params, *, n_heads: int, head_dim: int, n_state: int,
    eps: float = 1e-5,
) -> tuple[jax.Array, Params]:
    """One-step decode of :func:`mamba2_apply` (the recurrence itself).
    x: (B, 1, d)."""
    f32, dtype = jnp.float32, x.dtype
    b = x.shape[0]
    di = n_heads * head_dim
    zxbcdt = x[:, 0] @ params["in_proj"].astype(dtype)
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * n_state], axis=-1)
    win = jnp.concatenate([cache["conv"].astype(f32), xbc[:, None].astype(f32)], axis=1)
    conv = jnp.einsum("bkc,kc->bc", win, params["conv_w"].astype(f32))
    conv = conv + params["conv_b"].astype(f32)
    xs, bm, cm = jnp.split(jax.nn.silu(conv).astype(dtype).astype(f32), [di, di + n_state], axis=-1)
    xs = xs.reshape(b, n_heads, head_dim)
    dt = jax.nn.softplus(dt.astype(f32) + params["dt_bias"].astype(f32))  # (B, H)
    decay = jnp.exp(dt * -jnp.exp(params["a_log"].astype(f32)))
    st = cache["ssm"] * decay[..., None, None]
    st = st + (dt[..., None] * xs)[..., None] * bm[:, None, None, :]
    y = jnp.einsum("bhpn,bn->bhp", st, cm) + params["d_skip"].astype(f32)[:, None] * xs
    y = _gated_rmsnorm(y.reshape(b, di), z, params["norm_scale"], eps)
    out = (y @ params["out_proj"].astype(dtype))[:, None]
    return out, {"conv": win[:, 1:].astype(cache["conv"].dtype), "ssm": st}


# =====================================================================
# mLSTM (xLSTM matrix-memory block) — chunkwise parallel
# =====================================================================
def init_mlstm(rng, d: int, n_heads: int) -> Params:
    hd = d // n_heads
    ks = jax.random.split(rng, 7)
    return {
        "wq": _init(ks[0], (d, n_heads, hd)),
        "wk": _init(ks[1], (d, n_heads, hd)),
        "wv": _init(ks[2], (d, n_heads, hd)),
        "w_i": _init(ks[3], (d, n_heads)),
        "w_f": _init(ks[4], (d, n_heads)),
        "w_o": _init(ks[5], (d, d)),
        "out": _init(ks[6], (d, d)),
    }


def mlstm_apply(params: Params, x: jax.Array, chunk: int = 256) -> jax.Array:
    """Chunkwise-parallel mLSTM.  x: (B, S, d).

    Recurrence per head:  C_t = f_t C_{t-1} + i_t k_t v_tᵀ,
                          n_t = f_t n_{t-1} + i_t k_t,
                          h_t = (C_tᵀ q_t) / max(|n_t·q_t|, 1).
    Gates: f = sigmoid, i = sigmoid (stabilised variant; see module note).
    """
    bsz, s, d = x.shape
    dtype = x.dtype
    q = jnp.einsum("bsd,dhk->bhsk", x, params["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bhsk", x, params["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bhsk", x, params["wv"].astype(dtype))
    hd = q.shape[-1]
    k = k / np.sqrt(hd).astype(np.float32)
    igate = jax.nn.sigmoid(x @ params["w_i"].astype(dtype)).transpose(0, 2, 1)  # (B,H,S)
    fgate = jax.nn.sigmoid(x @ params["w_f"].astype(dtype)).transpose(0, 2, 1)

    _, chunk = _chunked(x, chunk)
    nc = s // chunk

    def c_split(t):
        return t.reshape(t.shape[0], t.shape[1], nc, chunk, *t.shape[3:])

    qc, kc, vc = c_split(q), c_split(k), c_split(v)
    ic = igate.reshape(bsz, -1, nc, chunk)
    fc = fgate.reshape(bsz, -1, nc, chunk)
    logf = jnp.log(fc.astype(jnp.float32) + 1e-9)
    lcum = jnp.cumsum(logf, axis=-1)  # (B,H,nc,chunk) cumulative log-decay

    n_heads_ = q.shape[1]
    c_state = jnp.zeros((bsz, n_heads_, hd, hd), jnp.float32)
    n_state = jnp.zeros((bsz, n_heads_, hd), jnp.float32)
    outs = []
    for c in range(nc):
        lc = lcum[:, :, c]  # (B,H,chunk)
        ltot = lc[..., -1:]  # (B,H,1)
        qf = qc[:, :, c].astype(jnp.float32)
        kf = kc[:, :, c].astype(jnp.float32)
        vf = vc[:, :, c].astype(jnp.float32)
        iw = ic[:, :, c].astype(jnp.float32)
        # intra-chunk: scores_ij = (q_i·k_j)·exp(L_i − L_j)·i_j for j ≤ i
        scores = jnp.einsum("bhik,bhjk->bhij", qf, kf)
        decay = jnp.exp(lc[:, :, :, None] - lc[:, :, None, :])
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        w = jnp.where(tri[None, None], scores * decay * iw[:, :, None, :], 0.0)
        intra = jnp.einsum("bhij,bhjk->bhik", w, vf)
        # inter-chunk: h_i += exp(L_i) · q_i @ C_prev ; n likewise
        qdec = qf * jnp.exp(lc)[..., None]
        inter = jnp.einsum("bhik,bhkl->bhil", qdec, c_state)
        num = intra + inter
        # normaliser n_i = Σ_{j≤i} exp(L_i − L_j)·i_j·k_j + exp(L_i)·n_prev
        wn = jnp.where(tri[None, None], decay * iw[:, :, None, :], 0.0)
        n_all = jnp.einsum("bhij,bhjk->bhik", wn, kf) + jnp.exp(lc)[..., None] * n_state[
            :, :, None, :
        ]
        denom = jnp.maximum(jnp.abs(jnp.einsum("bhik,bhik->bhi", qf, n_all)), 1.0)
        outs.append((num / denom[..., None]).astype(dtype))
        # carry states
        kdec = kf * jnp.exp(ltot - lc)[..., None] * iw[..., None]
        c_state = jnp.exp(ltot)[..., None] * c_state + jnp.einsum(
            "bhjk,bhjl->bhkl", kdec, vf
        )
        n_state = jnp.exp(ltot) * n_state + kdec.sum(axis=2)
    h = jnp.concatenate(outs, axis=2)  # (B,H,S,hd)
    h = h.transpose(0, 2, 1, 3).reshape(bsz, s, d)
    o = jax.nn.sigmoid(x @ params["w_o"].astype(dtype))
    return (h * o) @ params["out"].astype(dtype)


def init_mlstm_cache(batch: int, d: int, n_heads: int, dtype=jnp.float32) -> Params:
    hd = d // n_heads
    return {
        "c": jnp.zeros((batch, n_heads, hd, hd), dtype),
        "n": jnp.zeros((batch, n_heads, hd), dtype),
    }


def mlstm_decode(
    params: Params, x: jax.Array, cache: Params
) -> tuple[jax.Array, Params]:
    dtype = x.dtype
    xt = x[:, 0]
    q = jnp.einsum("bd,dhk->bhk", xt, params["wq"].astype(dtype)).astype(jnp.float32)
    k = jnp.einsum("bd,dhk->bhk", xt, params["wk"].astype(dtype)).astype(jnp.float32)
    v = jnp.einsum("bd,dhk->bhk", xt, params["wv"].astype(dtype)).astype(jnp.float32)
    hd = q.shape[-1]
    k = k / np.sqrt(hd)
    i = jax.nn.sigmoid(xt @ params["w_i"].astype(dtype)).astype(jnp.float32)  # (B,H)
    f = jax.nn.sigmoid(xt @ params["w_f"].astype(dtype)).astype(jnp.float32)
    c = f[..., None, None] * cache["c"] + i[..., None, None] * jnp.einsum(
        "bhk,bhl->bhkl", k, v
    )
    n = f[..., None] * cache["n"] + i[..., None] * k
    num = jnp.einsum("bhk,bhkl->bhl", q, c)
    denom = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", q, n)), 1.0)
    h = (num / denom[..., None]).reshape(xt.shape[0], -1).astype(dtype)
    o = jax.nn.sigmoid(xt @ params["w_o"].astype(dtype))
    out = ((h * o) @ params["out"].astype(dtype))[:, None]
    return out, {"c": c.astype(cache["c"].dtype), "n": n.astype(cache["n"].dtype)}


# =====================================================================
# sLSTM (xLSTM scalar-memory block) — sequential scan
# =====================================================================
def init_slstm(rng, d: int, n_heads: int) -> Params:
    hd = d // n_heads
    ks = jax.random.split(rng, 3)
    return {
        # input projections for gates i, f, z, o
        "w_in": _init(ks[0], (d, 4, d)),
        # block-diagonal recurrent weights per head: (4, H, hd, hd)
        "r": _init(ks[1], (4, n_heads, hd, hd), scale=1.0 / np.sqrt(hd)),
        "out": _init(ks[2], (d, d)),
    }


def _slstm_step(params: Params, carry, xg, n_heads: int):
    h, c, n = carry  # h, c, n: (B, d) float32
    bsz, d = h.shape
    hd = d // n_heads
    hh = h.reshape(bsz, n_heads, hd)
    rec = jnp.einsum("bhk,ghkl->gbhl", hh, params["r"].astype(jnp.float32)).reshape(
        4, bsz, d
    )
    g = xg + rec  # (4, B, d)
    i = jax.nn.sigmoid(g[0])
    f = jax.nn.sigmoid(g[1])
    z = jnp.tanh(g[2])
    o = jax.nn.sigmoid(g[3])
    c2 = f * c + i * z
    n2 = jnp.maximum(f * n + i, 1.0)
    h2 = o * (c2 / n2)
    return (h2, c2, n2), h2


def slstm_apply(params: Params, x: jax.Array, n_heads: int) -> jax.Array:
    bsz, s, d = x.shape
    dtype = x.dtype
    xg = jnp.einsum("bsd,dge->gbse", x, params["w_in"].astype(dtype)).astype(
        jnp.float32
    )  # (4,B,S,d)
    carry = (
        jnp.zeros((bsz, d), jnp.float32),
        jnp.zeros((bsz, d), jnp.float32),
        jnp.zeros((bsz, d), jnp.float32),
    )

    def step(carry, xt):
        return _slstm_step(params, carry, xt, n_heads)

    _, hs = jax.lax.scan(step, carry, xg.transpose(2, 0, 1, 3))  # scan over S
    h = hs.transpose(1, 0, 2).astype(dtype)  # (B,S,d)
    return h @ params["out"].astype(dtype)


def init_slstm_cache(batch: int, d: int, dtype=jnp.float32) -> Params:
    return {
        "h": jnp.zeros((batch, d), dtype),
        "c": jnp.zeros((batch, d), dtype),
        "n": jnp.zeros((batch, d), dtype),
    }


def slstm_decode(
    params: Params, x: jax.Array, cache: Params, n_heads: int
) -> tuple[jax.Array, Params]:
    dtype = x.dtype
    xg = jnp.einsum("bd,dge->gbe", x[:, 0], params["w_in"].astype(dtype)).astype(
        jnp.float32
    )
    carry = (
        cache["h"].astype(jnp.float32),
        cache["c"].astype(jnp.float32),
        cache["n"].astype(jnp.float32),
    )
    (h2, c2, n2), _ = _slstm_step(params, carry, xg, n_heads)
    out = (h2.astype(dtype) @ params["out"].astype(dtype))[:, None]
    return out, {
        "h": h2.astype(cache["h"].dtype),
        "c": c2.astype(cache["c"].dtype),
        "n": n2.astype(cache["n"].dtype),
    }
