"""Pallas kernel validation: sweep shapes/dtypes, assert_allclose against
the pure-jnp oracles (interpret=True executes the kernel body on CPU)."""

import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import decode_attention, flash_attention, moe_gating, rmsnorm
from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-5, atol=2e-5
    )


# ------------------------------------------------------- flash attention
@pytest.mark.parametrize(
    "b,h,kv,s,hd",
    [
        (1, 4, 4, 128, 64),     # MHA
        (2, 8, 2, 256, 64),     # GQA 4:1
        (1, 4, 1, 128, 128),    # MQA
        (2, 2, 2, 64, 32),      # small block (block > seq clamps)
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(b, h, kv, s, hd, dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, h, s, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(b, kv, s, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(b, kv, s, hd)), dtype)
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


def test_flash_attention_lengths_mask_padded_batch():
    """The ORLOJ padded-batch model: short requests padded to the max must
    be numerically identical to running them alone."""
    rng = np.random.default_rng(1)
    b, h, s, hd = 3, 4, 128, 64
    q = jnp.asarray(rng.normal(size=(b, h, s, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, s, hd)), jnp.float32)
    lengths = jnp.array([128, 70, 17], jnp.int32)
    out = flash_attention(q, k, v, lengths, block_q=64, block_k=64)
    for i, L in enumerate([128, 70, 17]):
        alone = flash_attention(
            q[i : i + 1, :, :L], k[i : i + 1, :, :L], v[i : i + 1, :, :L],
            block_q=64, block_k=64,
        )
        np.testing.assert_allclose(
            np.asarray(out[i, :, :L], np.float32),
            np.asarray(alone[0], np.float32),
            rtol=2e-5,
            atol=2e-5,
        )


@pytest.mark.parametrize("window", [16, 64])
def test_flash_attention_sliding_window(window):
    rng = np.random.default_rng(2)
    b, h, s, hd = 1, 2, 128, 32
    q = jnp.asarray(rng.normal(size=(b, h, s, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, s, hd)), jnp.float32)
    out = flash_attention(q, k, v, window=window, block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), rtol=2e-5, atol=2e-5
    )


def test_flash_attention_noncausal():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 2, 64, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 64, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 64, 32)), jnp.float32)
    out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), rtol=2e-5, atol=2e-5
    )


# ------------------------------------------------------- decode attention
@pytest.mark.parametrize(
    "b,h,kv,s,hd",
    [(2, 8, 2, 512, 64), (1, 4, 4, 256, 128), (4, 8, 1, 1024, 64)],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_shapes(b, h, kv, s, hd, dtype):
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(b, h, hd)), dtype)
    kc = jnp.asarray(rng.normal(size=(b, kv, s, hd)), dtype)
    vc = jnp.asarray(rng.normal(size=(b, kv, s, hd)), dtype)
    valid = jnp.asarray(rng.integers(1, s + 1, size=(b,)), jnp.int32)
    out = decode_attention(q, kc, vc, valid, block_k=128)
    want = ref.decode_attention_ref(q, kc, vc, valid)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize(
    "s,block_k",
    [
        (300, 256),   # S % bk != 0: the cache pads to 512, two tiles
        (96, 64),     # pads to 128
        (7, 256),     # S < bk: bk clamps to 8, the cache pads to 8
        (130, 128),   # pads to 256, the second tile holds 2 live rows
    ],
)
def test_decode_attention_nondivisible_cache_length(s, block_k):
    """Regression: S % block_k != 0 used to trip the divisor assert."""
    rng = np.random.default_rng(9)
    b, h, kv, hd = 2, 4, 2, 64
    q = jnp.asarray(rng.normal(size=(b, h, hd)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(b, kv, s, hd)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b, kv, s, hd)), jnp.float32)
    valid = jnp.asarray(rng.integers(1, s + 1, size=(b,)), jnp.int32)
    out = decode_attention(q, kc, vc, valid, block_k=block_k)
    want = ref.decode_attention_ref(q, kc, vc, valid)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=2e-5, atol=2e-5,
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_pallas_pads_cache_in_interpreter(dtype):
    """The kernel itself (interpret mode) at a cache length that is not a
    multiple of block_k: the padded tail is masked, including for rows
    whose valid length reaches the last real slot."""
    rng = np.random.default_rng(11)
    b, h, kv, s, hd = 4, 8, 2, 300, 64
    q = jnp.asarray(rng.normal(size=(b, h, hd)), dtype)
    kc = jnp.asarray(rng.normal(size=(b, kv, s, hd)), dtype)
    vc = jnp.asarray(rng.normal(size=(b, kv, s, hd)), dtype)
    valid = jnp.array([s, 1, 257, 0], jnp.int32)
    out = decode_attention_pallas(q, kc, vc, valid, block_k=256, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, valid)
    assert out.shape == (b, h, hd)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


def test_decode_attention_rejects_unaligned_block():
    q = jnp.zeros((1, 2, 64), jnp.float32)
    kc = jnp.zeros((1, 2, 64, 64), jnp.float32)
    with pytest.raises(ValueError, match="multiple of 8"):
        decode_attention_pallas(
            q, kc, kc, jnp.array([3], jnp.int32), block_k=12, interpret=True
        )


def test_importing_kernels_initialises_no_backend():
    """Interpret-vs-compiled is decided when a kernel is traced: importing
    the kernels must not start a backend (on a TPU host that would take
    the chip)."""
    code = (
        "import repro.kernels, repro.serving.engine\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, list(xla_bridge._backends)\n"
    )
    env = {**os.environ, "PYTHONPATH": os.pathsep.join(sys.path)}
    subprocess.run([sys.executable, "-c", code], check=True, env=env, timeout=120)


def test_decode_attention_empty_rows():
    """valid_len == 0 rows (freshly admitted, cache unwritten) must produce
    zeros — not NaN from a 0/0 softmax — and must not disturb live rows."""
    rng = np.random.default_rng(10)
    b, h, kv, s, hd = 3, 4, 2, 128, 64
    q = jnp.asarray(rng.normal(size=(b, h, hd)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(b, kv, s, hd)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b, kv, s, hd)), jnp.float32)
    valid = jnp.array([0, 77, 0], jnp.int32)
    out = decode_attention(q, kc, vc, valid, block_k=64)
    want = ref.decode_attention_ref(q, kc, vc, valid)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)
    np.testing.assert_array_equal(np.asarray(out[2]), 0.0)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=2e-5, atol=2e-5,
    )


def test_decode_attention_matches_flash_last_row():
    """Decoding the last position must equal the last row of full flash."""
    rng = np.random.default_rng(5)
    b, h, s, hd = 1, 4, 128, 64
    q = jnp.asarray(rng.normal(size=(b, h, s, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, s, hd)), jnp.float32)
    full = flash_attention(q, k, v, block_q=64, block_k=64)
    dec = decode_attention(
        q[:, :, -1], k, v, jnp.array([s], jnp.int32), block_k=64
    )
    np.testing.assert_allclose(
        np.asarray(full[:, :, -1], np.float32),
        np.asarray(dec, np.float32),
        rtol=2e-5,
        atol=2e-5,
    )


# --------------------------------------------------------------- rmsnorm
@pytest.mark.parametrize("t,d", [(256, 128), (512, 1024), (64, 896)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(t, d, dtype):
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(t, d)) * 3, dtype)
    scale = jnp.asarray(rng.normal(size=(d,)), jnp.float32)
    out = rmsnorm(x, scale)
    want = ref.rmsnorm_ref(x, scale)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


def test_rmsnorm_nd_input():
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(2, 128, 64)), jnp.float32)
    scale = jnp.ones((64,), jnp.float32)
    out = rmsnorm(x, scale)
    want = ref.rmsnorm_ref(x.reshape(-1, 64), scale).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5)


# ------------------------------------------------------------ moe gating
@pytest.mark.parametrize("t,e,k", [(256, 16, 4), (512, 128, 2), (256, 8, 1)])
def test_moe_gating(t, e, k):
    rng = np.random.default_rng(8)
    logits = jnp.asarray(rng.normal(size=(t, e)) * 2, jnp.float32)
    gates, idx = moe_gating(logits, k)
    wg, wi = ref.moe_gating_ref(logits, k)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(wi))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(wg), rtol=1e-5, atol=1e-6)
    # gates normalised over the selected experts
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-5)
