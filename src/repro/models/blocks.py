"""Decoder blocks: standard attention+MLP/MoE, Hymba hybrid (parallel
attention ∥ Mamba heads), xLSTM (mLSTM / sLSTM cells), and Mamba-2 + MLP
(Granite 4.0-H, beside its attention blocks)."""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from . import moe as moe_lib
from . import ssm
from .config import ModelConfig
from .layers import (
    attention_apply,
    attention_decode,
    init_attention,
    init_kv_cache,
    init_mlp,
    init_norm,
    mlp_apply,
    norm_apply,
)

Params = dict[str, Any]


def block_kind(cfg: ModelConfig, layer_idx: int) -> str:
    if cfg.layer_pattern:
        return {"M": "mamba2", "A": "attn"}[cfg.layer_pattern[layer_idx % len(cfg.layer_pattern)]]
    if cfg.block_pattern == "xlstm":
        return (
            "slstm"
            if (layer_idx % cfg.slstm_every) == cfg.slstm_every - 1
            else "mlstm"
        )
    if cfg.block_pattern == "hymba":
        return "hymba"
    return "attn"


# ------------------------------------------------------------------ init
def init_block(rng, cfg: ModelConfig, layer_idx: int) -> Params:
    kind = block_kind(cfg, layer_idx)
    d = cfg.d_model
    ks = jax.random.split(rng, 8)
    p: Params = {"norm1": init_norm(ks[0], d, cfg.norm)}
    if kind == "mlstm":
        p["cell"] = ssm.init_mlstm(ks[1], d, cfg.n_heads)
        return p
    if kind == "slstm":
        p["cell"] = ssm.init_slstm(ks[1], d, cfg.n_heads)
        return p
    if kind == "mamba2":
        p["mamba2"] = ssm.init_mamba2(
            ks[1], d, cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state, cfg.mamba_conv
        )
    else:
        p["attn"] = init_attention(
            ks[1], d, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
        )
    if kind == "hymba":
        p["mamba"] = ssm.init_mamba(ks[2], d, cfg.ssm_state)
        p["norm_attn"] = init_norm(ks[3], d, "rmsnorm")
        p["norm_ssm"] = init_norm(ks[4], d, "rmsnorm")
    p["norm2"] = init_norm(ks[5], d, cfg.norm)
    if cfg.is_moe:
        p["moe"] = moe_lib.init_moe(ks[6], d, cfg.d_ff, cfg.n_experts)
        if cfg.moe_dense_residual:
            p["mlp"] = init_mlp(ks[7], d, cfg.d_ff, cfg.mlp)
    elif cfg.mlp != "none":
        p["mlp"] = init_mlp(ks[6], d, cfg.d_ff, cfg.mlp)
    return p


# --------------------------------------------------------------- forward
def _mamba2_sizes(cfg: ModelConfig) -> dict:
    return dict(n_heads=cfg.mamba_heads, head_dim=cfg.mamba_head_dim, n_state=cfg.ssm_state)


def _residual(cfg: ModelConfig, y: jax.Array) -> jax.Array:
    """A residual branch's output, times ``residual_multiplier`` (Granite)."""
    return y if cfg.residual_multiplier == 1.0 else y * cfg.residual_multiplier


def _tp_only_constraints(params: Params) -> Params:
    """Constrain weight leaves to their tensor-parallel-only layout: GSPMD
    then materialises them via a weight all-gather over the FSDP axis
    rather than partial-summing activations (§Perf pair 2)."""
    from jax.sharding import PartitionSpec as P

    specs = {
        "wq": P(None, "model", None),
        "wk": P(None, "model", None),
        "wv": P(None, "model", None),
        "wo": P("model", None, None),
        "w_gate": P(None, "model"),
        "w_up": P(None, "model"),
        "w_down": P("model", None),
    }

    def rule(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        spec = specs.get(name)
        if spec is not None and len(spec) == leaf.ndim:
            # (A bf16-cast-before-gather variant was measured and REFUTED:
            # GSPMD hoists the convert after the gather, so the all-gather
            # stays f32 while the cast breaks the partial-sum elimination —
            # see EXPERIMENTS.md §Perf pair 2, iteration 4.)
            try:
                return jax.lax.with_sharding_constraint(leaf, spec)
            except Exception:
                return leaf
        return leaf

    return jax.tree_util.tree_map_with_path(rule, params)


def block_apply(
    params: Params, x: jax.Array, cfg: ModelConfig, layer_idx: int
) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward.  Returns (x, aux_loss)."""
    if cfg.fsdp_weight_gather:
        params = _tp_only_constraints(params)
    kind = block_kind(cfg, layer_idx)
    aux = jnp.zeros((), jnp.float32)
    h = norm_apply(params["norm1"], x, cfg.norm)
    # Bound the unrolled cross-chunk carry to ≤32 iterations regardless of
    # sequence length (HLO size / compile time), growing the chunk instead.
    chunk = max(cfg.mlstm_chunk, x.shape[1] // 32)
    if kind == "mlstm":
        return x + ssm.mlstm_apply(params["cell"], h, chunk), aux
    if kind == "slstm":
        return x + ssm.slstm_apply(params["cell"], h, cfg.n_heads), aux

    if kind == "mamba2":
        x = x + _residual(cfg, ssm.mamba2_apply(
            params["mamba2"], h, chunk=cfg.mamba_chunk, **_mamba2_sizes(cfg)
        ))
    else:
        attn_out = attention_apply(
            params["attn"],
            h,
            n_kv=cfg.n_kv_heads,
            rope_theta=cfg.rope_theta,
            sliding_window=cfg.sliding_window,
            softcap=cfg.logit_softcap,
            repeat_kv=cfg.gqa_repeat_kv,
            rope=cfg.position_embedding == "rope",
            scale=cfg.attention_multiplier,
        )
        if kind == "hymba":
            ssm_out = ssm.mamba_apply(params["mamba"], h, chunk)
            mix = 0.5 * (
                norm_apply(params["norm_attn"], attn_out, "rmsnorm")
                + norm_apply(params["norm_ssm"], ssm_out, "rmsnorm")
            )
            x = x + mix
        else:
            x = x + _residual(cfg, attn_out)

    h2 = norm_apply(params["norm2"], x, cfg.norm)
    if cfg.is_moe:
        y, aux = moe_lib.moe_apply(
            params["moe"], h2, top_k=cfg.top_k, capacity_factor=cfg.capacity_factor
        )
        if cfg.moe_dense_residual:
            y = y + mlp_apply(params["mlp"], h2, cfg.mlp)
        x = x + y
    elif cfg.mlp != "none":
        x = x + _residual(cfg, mlp_apply(params["mlp"], h2, cfg.mlp))
    return x, aux


# ----------------------------------------------------------------- cache
def init_block_cache(
    cfg: ModelConfig, layer_idx: int, batch: int, cache_len: int, dtype=jnp.bfloat16
) -> Params:
    kind = block_kind(cfg, layer_idx)
    d = cfg.d_model
    if kind == "mlstm":
        return {"cell": ssm.init_mlstm_cache(batch, d, cfg.n_heads)}
    if kind == "slstm":
        return {"cell": ssm.init_slstm_cache(batch, d)}
    if kind == "mamba2":
        return {"mamba2": ssm.init_mamba2_cache(
            batch, conv_w=cfg.mamba_conv, dtype=dtype, **_mamba2_sizes(cfg)
        )}
    eff_len = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
    c: Params = {
        "kv": init_kv_cache(
            batch, cfg.n_kv_heads, eff_len, cfg.resolved_head_dim, dtype
        )
    }
    if kind == "hymba":
        c["mamba"] = ssm.init_mamba_cache(batch, d, cfg.ssm_state)
    return c


def block_decode(
    params: Params,
    x: jax.Array,
    cache: Params,
    pos: jax.Array,
    cfg: ModelConfig,
    layer_idx: int,
) -> tuple[jax.Array, Params]:
    """One-token decode step."""
    kind = block_kind(cfg, layer_idx)
    h = norm_apply(params["norm1"], x, cfg.norm)
    if kind == "mlstm":
        out, c2 = ssm.mlstm_decode(params["cell"], h, cache["cell"])
        return x + out, {"cell": c2}
    if kind == "slstm":
        out, c2 = ssm.slstm_decode(params["cell"], h, cache["cell"], cfg.n_heads)
        return x + out, {"cell": c2}

    if kind == "mamba2":
        out, c2 = ssm.mamba2_decode(params["mamba2"], h, cache["mamba2"], **_mamba2_sizes(cfg))
        x = x + _residual(cfg, out)
        new_cache: Params = {"mamba2": c2}
    else:
        attn_out, kv2 = attention_decode(
            params["attn"],
            h,
            cache["kv"],
            pos,
            n_kv=cfg.n_kv_heads,
            rope_theta=cfg.rope_theta,
            sliding_window=cfg.sliding_window,
            softcap=cfg.logit_softcap,
            rope=cfg.position_embedding == "rope",
            scale=cfg.attention_multiplier,
        )
        new_cache = {"kv": kv2}
        if kind == "hymba":
            ssm_out, mc2 = ssm.mamba_decode(params["mamba"], h, cache["mamba"])
            mix = 0.5 * (
                norm_apply(params["norm_attn"], attn_out, "rmsnorm")
                + norm_apply(params["norm_ssm"], ssm_out, "rmsnorm")
            )
            x = x + mix
            new_cache["mamba"] = mc2
        else:
            x = x + _residual(cfg, attn_out)

    h2 = norm_apply(params["norm2"], x, cfg.norm)
    if cfg.is_moe:
        y, _ = moe_lib.moe_apply(
            params["moe"], h2, top_k=cfg.top_k, capacity_factor=cfg.capacity_factor
        )
        if cfg.moe_dense_residual:
            y = y + mlp_apply(params["mlp"], h2, cfg.mlp)
        x = x + y
    elif cfg.mlp != "none":
        x = x + _residual(cfg, mlp_apply(params["mlp"], h2, cfg.mlp))
    return x, new_cache
