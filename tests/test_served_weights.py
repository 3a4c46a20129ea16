"""The executor's served weight copy (``Model.serving_params``): made once
per assignment of the weights, never per batch, and it changes the logits
by nothing but the rounding of the listed weights to the compute dtype.

On a TPU a default-precision product rounds its operands to bfloat16, so
there that rounding is what the forward did already (the compiled program
for a v5e is checked in ``test_tpu_compile.py``).  The CPU multiplies
float32 exactly, so here the served logits are compared bit for bit with
those of the float32 weights rounded the same way.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.core import EmpiricalDistribution, OrlojScheduler, SchedulerConfig
from repro.models import Model
from repro.models.layers import COMPUTE_DTYPE_LEAVES
from repro.serving.engine import EngineConfig, JaxExecutor, ServingEngine
from test_arch_smoke import _batch

ENGINE = EngineConfig(buckets=(16, 32), batch_sizes=(1, 2), profile_reps=1)
TOKEN_DENSE = [a for a in ARCHS if get_config(a).arch_type == "dense"]


def _model(arch: str, dtype: str, **kw) -> Model:
    return Model(get_config(arch).reduced(dtype=dtype, **kw))


def _rounded(served, params):
    """The master weights rounded as the served copy holds them, in float32."""
    return jax.tree.map(lambda s, p: s.astype(p.dtype), served, params)


def _named_leaves(tree):
    return [
        (getattr(path[-1], "key", None), leaf)
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    ]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("arch", ARCHS)
def test_served_logits_equal_the_masters_logits_bit_for_bit(arch, dtype):
    model = _model(arch, dtype)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(model, jax.random.PRNGKey(1))
    served = model.serving_params(params)
    fwd = jax.jit(model.logits)
    got = np.asarray(fwd(served, batch))
    cast = [n for (n, s), p in zip(_named_leaves(served), jax.tree.leaves(params))
            if s.dtype != p.dtype]
    if dtype == "float32":  # already the compute dtype: nothing to cast
        assert cast == []
        np.testing.assert_array_equal(got, np.asarray(fwd(params, batch)))
    else:
        assert cast and set(cast) <= COMPUTE_DTYPE_LEAVES, cast
        np.testing.assert_array_equal(got, np.asarray(fwd(_rounded(served, params), batch)))


@pytest.mark.parametrize("arch", TOKEN_DENSE)
def test_the_executor_forward_runs_on_the_served_copy(arch):
    model = _model(arch, "bfloat16")
    params = model.init(jax.random.PRNGKey(0))
    ex = JaxExecutor(model, params, ENGINE)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, model.cfg.vocab_size)}
    want = jax.jit(model.logits)(_rounded(ex._served, params), batch)
    np.testing.assert_array_equal(np.asarray(ex._fwd(ex._served, batch)), np.asarray(want))
    ms, k_pad = ex._run(np.asarray(batch["tokens"]))
    assert ms > 0.0 and k_pad == 2


@pytest.mark.parametrize("arch", ["orloj_gpt", "olmo_1b"])
def test_served_leaves_are_in_the_compute_dtype_and_the_rest_stay_float32(arch):
    model = _model(arch, "bfloat16", scan_layers=True)
    params = model.init(jax.random.PRNGKey(0))
    ex = JaxExecutor(model, params, ENGINE)
    assert ex.params is params  # the masters, exactly as assigned
    assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(ex.params))
    listed = 0
    for (name, s), p in zip(_named_leaves(ex._served), jax.tree.leaves(params)):
        assert s.shape == p.shape
        if name in COMPUTE_DTYPE_LEAVES:
            assert s.dtype == jnp.bfloat16, name
            listed += p.nbytes
        else:  # layernorm scale and bias
            assert s.dtype == jnp.float32 and name in ("scale", "bias"), name
    assert ex.cast_bytes_saved == listed > 0


def test_each_assignment_makes_one_copy_and_serving_makes_none():
    cfg = get_config("orloj_gpt").reduced(dtype="bfloat16", n_layers=1, d_model=64, vocab_size=128)
    eng = ServingEngine(cfg, ENGINE, seed=0)
    ex = eng.executor
    assert ex.n_weight_casts == 1
    ex.params = None  # frees the copy, makes none
    assert ex.params is None and ex._served is None and ex.n_weight_casts == 1
    new = eng.model.init(jax.random.PRNGKey(7))
    ex.params = new
    assert ex.n_weight_casts == 2 and ex.params is new
    np.testing.assert_array_equal(
        np.asarray(ex._served["embed"]["table"]),
        np.asarray(new["embed"]["table"].astype(jnp.bfloat16)),
    )
    lm = eng.profile_latency_model()
    reqs, hist = eng.make_requests(
        16, lm, length_sampler=lambda rng: int(rng.integers(4, 32)),
        slo_scale=50.0, utilization=0.5, seed=1,
    )
    dists = {a: EmpiricalDistribution.from_samples(x) for a, x in hist.items() if len(x) >= 2}
    scheds = [OrlojScheduler(lm, cfg=SchedulerConfig(batch_sizes=(1, 2)), initial_dists=dists)
              for _ in range(2)]
    res = eng.serve_pool(reqs, scheds)
    assert res.n_batches > 0
    assert ex.n_weight_casts == 2


_CONVERT = re.compile(r"stablehlo\.convert %\S+ : \(tensor<([0-9x]+)xf32>\)")


def _f32_converted_shapes(text: str) -> set[tuple[int, ...]]:
    return {tuple(int(d) for d in m.group(1).split("x")) for m in _CONVERT.finditer(text)}


def test_the_served_forward_converts_no_float32_weight():
    model = _model("orloj_gpt", "bfloat16", scan_layers=True)
    ex = JaxExecutor(model, model.init(jax.random.PRNGKey(0)), ENGINE)
    batch = {"tokens": jnp.ones((2, 16), jnp.int32)}
    weights = {p.shape for n, p in _named_leaves(ex.params) if n in COMPUTE_DTYPE_LEAVES}
    assert (model.cfg.n_layers, model.cfg.d_model, model.cfg.d_ff) in weights  # stacked
    served = _f32_converted_shapes(ex._fwd.lower(ex._served, batch).as_text())
    assert not served & weights, served & weights
    # the masters' program does convert one: the embedding table
    masters = _f32_converted_shapes(ex._fwd.lower(ex.params, batch).as_text())
    assert (model.cfg.vocab_size, model.cfg.d_model) in masters & weights
