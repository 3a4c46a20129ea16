"""Compile the serving path's kernels, the full-width ``orloj_gpt``
forward and the full-width Granite-4.0-H-Micro forward for one described
TPU v5e chip.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached.  It refuses what interpret mode
accepts — blocks that break the (8, 128) tiling rule, kernels that ask for
too much VMEM, programs that do not fit HBM — so these tests guard the chip
path at no chip time.  Nothing runs, so they say nothing about results or
times.  The topology is described inside a fixture (never at import) and
this is the only test file that describes it.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.moe_gating import moe_gating_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.models import Model
from repro.serving.engine import EngineConfig, JaxExecutor

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "no TPU compiler here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache off while compiling.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize(
    "b,h,kv,s,hd,dtype",
    [
        (8, 12, 12, 256, 64, jnp.float32),  # orloj_gpt, the decode executor's cache
        (8, 12, 12, 256, 64, jnp.bfloat16),
        (8, 12, 12, 300, 64, jnp.bfloat16),  # S % block_k != 0: cache padded
        (8, 32, 8, 1024, 128, jnp.bfloat16),  # GQA 4:1, 128-wide heads
    ],
)
def test_decode_attention_compiles(one_chip, b, h, kv, s, hd, dtype):
    def fn(q, kc, vc, valid):
        return decode_attention_pallas(q, kc, vc, valid, interpret=False)

    compiled = _compile(
        fn,
        _spec((b, h, hd), dtype, one_chip),
        _spec((b, kv, s, hd), dtype, one_chip),
        _spec((b, kv, s, hd), dtype, one_chip),
        _spec((b,), jnp.int32, one_chip),
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_compiles(one_chip, dtype):
    b, h, s, hd = 8, 12, 256, 64

    def fn(q, k, v, lengths):
        return flash_attention_pallas(q, k, v, lengths, interpret=False)

    qkv = _spec((b, h, s, hd), dtype, one_chip)
    compiled = _compile(fn, qkv, qkv, qkv, _spec((b,), jnp.int32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_rmsnorm_compiles(one_chip):
    def fn(x, scale):
        return rmsnorm_pallas(x, scale, interpret=False)

    compiled = _compile(
        fn,
        _spec((2048, 768), jnp.bfloat16, one_chip),
        _spec((768,), jnp.float32, one_chip),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_moe_gating_compiles(one_chip):
    def fn(logits):
        return moe_gating_pallas(logits, 2, interpret=False)

    compiled = _compile(fn, _spec((2048, 16), jnp.float32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_orloj_gpt_padded_forward_fits_one_chip(one_chip):
    """The engine's prefill program (``JaxExecutor``: jitted ``model.logits``)
    at full published width and its largest (batch, bucket) shape."""
    model = Model(get_config("orloj_gpt"))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip), params)
    tokens = _spec((8, 256), jnp.int32, one_chip)
    compiled = (
        jax.jit(lambda p, t: model.logits(p, {"tokens": t}))
        .lower(params, tokens)
        .compile()
    )
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        + mem.generated_code_size_in_bytes
    )
    assert 0 < total < V5E_HBM_BYTES, total


_PARAM_CONVERT = re.compile(r"= \S+ convert\(%p__(\w+?)__\.\d+\)")


def _converted_leaves(text: str) -> set[str]:
    """Keys of the weight leaves whose entry parameter the compiled program
    converts (``p['blocks'][0]['mlp']['w_up']`` is ``p__blocks___0___mlp____w_up__``)."""
    return {m.group(1).split("___")[-1].lstrip("_") for m in _PARAM_CONVERT.finditer(text)}


def test_orloj_gpt_served_forward_converts_no_weight(one_chip):
    """The executor's forward at full width: on the float32 masters the
    compiled program rounds the stacked layer weights and the embedding
    table to bfloat16 on every call; on the served copy it converts none."""
    model = Model(get_config("orloj_gpt"))
    ex = JaxExecutor(model, None, EngineConfig())
    masters = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    served = jax.eval_shape(model.serving_params, masters)
    batch = {"tokens": _spec((8, 256), jnp.int32, one_chip)}

    def converted(params):
        specs = jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip), params)
        return _converted_leaves(ex._fwd.lower(specs, batch).compile().as_text())

    assert {"wq", "wk", "wv", "wo", "w_up", "w_down", "table"} <= converted(masters)
    assert converted(served) == set()


def test_granite_hybrid_served_forward_fits_one_chip(one_chip):
    """Granite-4.0-H-Micro as the chip deployment holds it (every published
    width, bfloat16 masters, which the executor serves as they are) at the
    largest served shape, 4 x 1024: weights, output and temporaries fit."""
    import dataclasses

    model = Model(dataclasses.replace(get_config("granite_4_h_micro"), param_dtype="bfloat16"))
    ex = JaxExecutor(model, None, EngineConfig())
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip), params)
    compiled = ex._fwd.lower(params, {"tokens": _spec((4, 1024), jnp.int32, one_chip)}).compile()
    mem = compiled.memory_analysis()
    # the bf16 weights and the tokens, give or take the tiling of small leaves
    assert abs(mem.argument_size_in_bytes - (2 * 3_191_396_096 + 4 * 4 * 1024)) < 2**20
    assert mem.output_size_in_bytes == 2 * 4 * 1024 * 100_352  # bf16 logits
    total = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert total < V5E_HBM_BYTES, total
