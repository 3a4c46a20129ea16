"""Granite 4.0-H (Mamba-2 mixers beside NoPE GQA attention) on the CPU, at
a small size that keeps the period of ten layers (five Mamba-2, one
attention, four Mamba-2); one test stacks two periods.

The program is checked against the benchmark's plain reference
(``benchmarks/chip/references/granite_hybrid.py``, loaded by path: float32
products at ``Precision.HIGHEST``, the scan as the recurrence one position
at a time), the chunked SSD against the recurrence, right padding, the
fp8 control, decode through the cache, and the mixer's named scopes.

Gaps are the widest absolute logit difference over all positions and
vocabulary entries, in units of the reference logits' RMS at each
position, as the benchmark's check measures them.
"""

from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import Model, ModelConfig
from repro.models import ssm
from repro.serving.engine import EngineConfig, JaxExecutor

REF_PATH = Path(__file__).resolve().parents[1] / "benchmarks/chip/references/granite_hybrid.py"
CHIP_CONFIG = Path(__file__).resolve().parents[1] / "benchmarks/chip/configs/granite_4_h_micro.json"

SMALL = dict(
    arch_type="hybrid", n_layers=10, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
    vocab_size=256, norm="rmsnorm", mlp="swiglu", layer_pattern="MMMMMAMMMM",
    mamba_heads=4, mamba_head_dim=16, ssm_state=8, mamba_conv=4, mamba_chunk=16,
    position_embedding="nope", attention_multiplier=1 / 16, embedding_multiplier=12.0,
    residual_multiplier=0.22, logits_scaling=8.0, tie_embeddings=True,
)
# float32 throughout: the same arithmetic as the reference but for the order
# of its sums and the chunked form of the scan (measured: 3e-6).
F32_GAP = 1e-4
# bfloat16 weights, activations and logits: the rounding of the residual
# stream and of the logits.  Measured on the CPU at this width over six
# seeds: 0.038-0.050 with 10 layers, 0.058-0.083 with 20; the float8
# control reads 0.27-0.34 and 0.40-0.61.
BF16_GAP = 0.12
ENGINE = EngineConfig(buckets=(16, 32, 64), batch_sizes=(1, 2), profile_reps=1)


def _ref():
    spec = importlib.util.spec_from_file_location("granite_hybrid_reference", REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _ref()


def _model_dict(dtype: str, **kw) -> dict:
    return dict(SMALL, dtype=dtype, param_dtype=dtype, **kw)


def _gap(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    rms = np.sqrt(np.mean(want * want, axis=-1, keepdims=True))
    return float(np.max(np.abs(got - want) / rms))


def _tokens(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(1, SMALL["vocab_size"], n).astype(np.int32)


@pytest.mark.parametrize("path", ["logits", "executor"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_program_matches_the_reference(dtype, path):
    m = _model_dict(dtype)
    model = Model(ModelConfig(name="granite-small", **m))
    assert model.unit == 10
    weights = REF.make_weights(m, 1234)
    toks = _tokens(48)
    if path == "logits":
        with jax.default_matmul_precision("highest"):
            got = jax.jit(model.logits)(weights, {"tokens": toks[None]})[0]
    else:  # the served forward, on the executor's served copy
        ex = JaxExecutor(model, weights, ENGINE)
        with jax.default_matmul_precision("highest"):
            got = ex._fwd(ex._served, {"tokens": toks[None]})[0]
    assert got.dtype == jnp.dtype(dtype)
    gap = _gap(got, REF.make_forward(m)(weights, toks))
    assert gap <= (F32_GAP if dtype == "float32" else BF16_GAP), gap


def test_two_periods_match_the_reference():
    m = _model_dict("bfloat16", n_layers=20)
    model = Model(ModelConfig(name="granite-small", **m))
    assert model.unit == 10 and model.n_units == 2
    weights = REF.make_weights(m, 4321)
    toks = _tokens(40)
    got = jax.jit(model.logits)(weights, {"tokens": toks[None]})[0]
    assert _gap(got, REF.make_forward(m)(weights, toks)) <= BF16_GAP


def test_the_fp8_control_fails_the_tolerance():
    m = _model_dict("bfloat16")
    weights = REF.make_weights(m, 1234)
    toks = _tokens(48)
    want = REF.make_forward(m)(weights, toks)
    gap = _gap(REF.make_forward(m, "fp8")(weights, toks), want)
    assert gap > BF16_GAP, gap


def test_bf16_masters_are_served_without_a_second_copy():
    m = _model_dict("bfloat16")
    weights = REF.make_weights(m, 7)
    ex = JaxExecutor(Model(ModelConfig(name="granite-small", **m)), weights, ENGINE)
    assert all(s is w for s, w in zip(jax.tree.leaves(ex._served), jax.tree.leaves(weights)))
    assert ex.cast_bytes_saved == 0


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_reference_weights_have_the_programs_layout(dtype):
    m = _model_dict(dtype)
    want = jax.eval_shape(Model(ModelConfig(name="g", **m)).init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: REF.make_weights(m, 3))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert [(x.shape, x.dtype) for x in jax.tree.leaves(got)] == [
        (x.shape, x.dtype) for x in jax.tree.leaves(want)
    ]


def _recurrence(x, dt, a, bm, cm):
    """h_t = exp(dt_t a) h_{t-1} + dt_t x_t ⊗ B_t; y_t = h_t · C_t."""
    b, s, h, p = x.shape
    y = np.zeros((b, s, h, p))
    state = np.zeros((b, h, p, bm.shape[-1]))
    for t in range(s):
        state = np.exp(dt[:, t] * a)[..., None, None] * state + (
            (dt[:, t, :, None] * x[:, t])[..., None] * bm[:, t, None, None, :]
        )
        y[:, t] = np.einsum("bhpn,bn->bhp", state, cm[:, t])
    return y


@pytest.mark.parametrize(
    "length,chunk",
    [(64, 16), (37, 8), (20, 64), (1, 4)],
    ids=["whole-chunks", "ragged-last-chunk", "chunk-longer-than-S", "one-position"],
)
def test_the_chunked_ssd_matches_the_recurrence(length, chunk):
    rng = np.random.default_rng(length)
    b, h, p, n = 2, 3, 4, 5
    x = rng.normal(size=(b, length, h, p)).astype(np.float32)
    dt = np.log1p(np.exp(rng.normal(-1.0, 1.0, size=(b, length, h)))).astype(np.float32)
    a = -np.arange(1.0, h + 1, dtype=np.float32)
    bm = rng.normal(size=(b, length, n)).astype(np.float32)
    cm = rng.normal(size=(b, length, n)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ssm.ssd(jnp.asarray(x), jnp.asarray(dt), jnp.asarray(a),
                                 jnp.asarray(bm), jnp.asarray(cm), chunk))
    want = _recurrence(x.astype(np.float64), dt.astype(np.float64), a, bm, cm)
    # float32 sums of exp-weighted terms against float64: rounding only
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4 * np.abs(want).max())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_right_padding_leaves_real_positions_unchanged(dtype):
    m = _model_dict(dtype)
    model = Model(ModelConfig(name="granite-small", **m))
    weights = REF.make_weights(m, 99)
    fwd = jax.jit(model.logits)
    real = _tokens(21, seed=1)
    zeros = np.concatenate([real, np.zeros(43, np.int32)])
    other = np.concatenate([real, _tokens(43, seed=2)])
    out = np.asarray(fwd(weights, {"tokens": np.stack([zeros, other])}).astype(jnp.float32))
    # whatever follows, a real position computes the same numbers (the conv,
    # the SSD and attention are causal, and padding adds exact zeros)
    np.testing.assert_array_equal(out[0, :21], out[1, :21])
    assert not np.array_equal(out[0, 21:], out[1, 21:])


def test_decode_through_the_cache_matches_the_forward():
    m = _model_dict("float32")
    model = Model(ModelConfig(name="granite-small", **m))
    weights = REF.make_weights(m, 5)
    toks = _tokens(12, seed=3)[None]
    with jax.default_matmul_precision("highest"):
        full = model.logits(weights, {"tokens": toks})
        cache = model.init_cache(1, cache_len=16, dtype=jnp.float32)
        step = jax.jit(model.decode_step)
        outs = []
        for i in range(toks.shape[1]):
            lg, cache = step(weights, toks[:, i : i + 1], cache, jnp.int32(i))
            outs.append(lg[:, 0])
    # float32 both ways; the recurrence against the chunked form: rounding only
    assert _gap(jnp.stack(outs, axis=1), full) <= F32_GAP


@functools.cache
def _lowered_text() -> str:
    model = Model(get_config("granite_4_h_micro").reduced())
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((1, 16), jnp.int32)}
    return jax.jit(model.logits).lower(params, batch).as_text(debug_info=True)


@pytest.mark.parametrize("part", ["in", "conv", "ssd", "norm", "out"])
def test_the_mixer_parts_are_named_in_the_program(part):
    assert f"orloj.mamba2.{part}/" in _lowered_text()


def test_the_registered_config_is_the_benchmarks_published_one():
    import json

    spec = json.loads(CHIP_CONFIG.read_text())
    cfg = get_config("granite_4_h_micro")
    for k, v in spec["model"].items():
        if k != "param_dtype":  # bf16 masters are the chip deployment's choice
            assert getattr(cfg, k) == v, k
    assert cfg.d_model == spec["hidden_size"] and cfg.n_layers == spec["num_hidden_layers"]
    assert cfg.mamba_inner == spec["mamba_expand"] * spec["hidden_size"]
    kinds = ["A" if t == "attention" else "M" for t in spec["layer_types"]]
    assert kinds == [cfg.layer_pattern[i % 10] for i in range(cfg.n_layers)]
    params = jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert n == 3_191_396_096
    assert abs(cfg.n_params_estimate - n) / n < 1e-3
