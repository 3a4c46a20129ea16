"""Plain reference of one step of the decode executor's attention.

The step, as the serving engine states it: each active slot writes this
step's key and value at ring position ``valid % S`` and advances ``valid``
(capped at S); inactive slots pass through untouched.  Then every slot's
queries attend over its first ``valid`` cache positions, one query head per
group of ``n_heads / n_kv_heads``; a slot with nothing valid gives zeros.

Computed in float64 with numpy; it imports nothing of the program.  The
control (``precision="fp8"``) rounds q, K and V to float8 e4m3 first, the
step below the bfloat16 the model configuration states.
"""

from __future__ import annotations

import numpy as np


def _q8(x: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return x.astype(ml_dtypes.float8_e4m3fn).astype(np.float64)


def step(kc, vc, valid, active, q, nk, nv, precision: str = "f64"):
    """Returns (kc2, vc2, valid2, out) for one step; shapes as the engine's:
    kc/vc (B, KV, S, hd), valid (B,), active (B,), q (B, H, hd), nk/nv (B, KV, hd)."""
    kc2, vc2 = np.array(kc, copy=True), np.array(vc, copy=True)
    valid, active = np.asarray(valid), np.asarray(active, bool)
    s = kc2.shape[2]
    for b in np.flatnonzero(active):
        pos = int(valid[b]) % s
        kc2[b, :, pos, :] = nk[b]
        vc2[b, :, pos, :] = nv[b]
    valid2 = np.where(active, np.minimum(valid + 1, s), valid).astype(valid.dtype)
    cast = _q8 if precision == "fp8" else (lambda x: np.asarray(x, np.float64))
    qq, kk, vv = cast(q), cast(kc2), cast(vc2)
    bsz, h, hd = qq.shape
    kv = kk.shape[1]
    out = np.zeros((bsz, h, hd), np.float64)
    for b in range(bsz):
        n = int(valid2[b])
        if n == 0:
            continue
        qg = qq[b].reshape(kv, h // kv, hd)
        sc = np.einsum("kgd,ktd->kgt", qg, kk[b, :, :n, :]) / np.sqrt(hd)
        sc -= sc.max(-1, keepdims=True)
        p = np.exp(sc)
        p /= p.sum(-1, keepdims=True)
        out[b] = np.einsum("kgt,ktd->kgd", p, vv[b, :, :n, :]).reshape(h, hd)
    return kc2, vc2, valid2, out
