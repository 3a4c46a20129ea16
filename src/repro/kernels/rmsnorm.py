"""Fused RMSNorm Pallas TPU kernel.

Row tiles (bt × d) in VMEM; the reduction, rsqrt and scale are fused in one
pass (one HBM read + one write per element instead of the 3+ passes an
unfused lowering can take).  f32 accumulation regardless of input dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import resolve_interpret


def _kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (y * scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_pallas(
    x: jax.Array,
    scale: jax.Array,
    *,
    eps: float = 1e-6,
    block_rows: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """x: (T, d); scale: (d,)."""
    t, d = x.shape
    block_rows = min(block_rows, t)
    assert t % block_rows == 0
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(t // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=resolve_interpret(interpret),
    )(x, scale)
