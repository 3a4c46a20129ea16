"""End-to-end test of the real-execution serving engine (tiny model)."""

import numpy as np
import pytest

from repro.core import EmpiricalDistribution, OrlojScheduler, SchedulerConfig
from repro.models.config import ModelConfig
from repro.serving.engine import EngineConfig, ServingEngine

# Real jitted-model execution: excluded from the quick CI lane.
pytestmark = pytest.mark.slow

TINY = ModelConfig(
    name="tiny",
    arch_type="dense",
    n_layers=2,
    d_model=64,
    n_heads=4,
    n_kv_heads=2,
    d_ff=128,
    vocab_size=256,
    dtype="float32",
    scan_layers=False,
)


@pytest.fixture(scope="module")
def engine():
    return ServingEngine(
        TINY, EngineConfig(buckets=(16, 32), batch_sizes=(1, 2, 4), profile_reps=2)
    )


def test_profile_fits_eq3(engine):
    lm = engine.profile_latency_model()
    assert lm.c0 >= 0 and lm.c1 > 0
    # bigger work → bigger predicted latency
    assert lm.batch_time([32.0] * 4) > lm.batch_time([16.0])


def test_executor_reports_padded_batch_size(engine):
    """A k=3 batch pads up to the next supported size (4) and the executor
    reports that executed size — the quantity the profiler must fit
    against for estimates to match measurements."""
    assert engine.executor.padded_batch_size(3) == 4
    assert engine.executor.padded_batch_size(4) == 4
    assert engine.executor.padded_batch_size(9) == 9  # beyond the largest
    ms, k_pad = engine.executor._run(np.ones((3, 16), np.int32))
    assert k_pad == 4
    assert ms > 0.0


def test_pool_serving_real_execution(engine):
    """Two ORLOJ replicas sharing the measured JAX executor finish a light
    trace through the unified multi-worker loop."""
    lm = engine.profile_latency_model()
    reqs, hist = engine.make_requests(
        24,
        lm,
        length_sampler=lambda rng: int(rng.integers(4, 32)),
        slo_scale=50.0,
        utilization=0.4,
        seed=2,
    )
    dists = {
        a: EmpiricalDistribution.from_samples(x)
        for a, x in hist.items()
        if len(x) >= 2
    }
    scheds = [
        OrlojScheduler(
            lm, cfg=SchedulerConfig(batch_sizes=(1, 2, 4)), initial_dists=dists
        )
        for _ in range(2)
    ]
    res = engine.serve_pool(reqs, scheds)
    assert res.n_workers == 2
    assert (
        res.n_finished_ok + res.n_finished_late + res.n_dropped + res.n_unserved
        == 24
    )
    assert res.utilization <= 1.0 + 1e-9


def test_executor_for_device_holds_its_own_params(engine):
    """A replica bound to a device keeps its own copy of the params there,
    runs its batches there, and is built once per device."""
    import jax

    dev = jax.devices()[0]
    ex = engine.executor_for(device=dev)
    assert ex is not engine.executor
    assert engine.executor_for(device=dev) is ex
    assert all(x.devices() == {dev} for x in jax.tree.leaves(ex.params))
    assert all(x.devices() == {dev} for x in jax.tree.leaves(ex._served))
    assert ex.n_weight_casts == 1
    ms, k_pad = ex._run(np.ones((2, 16), np.int32))
    assert k_pad == 2 and ms > 0.0
    slow = engine.executor_for(2.0, device=dev)
    assert slow.inner is ex and slow.scale == 2.0


POOL_ON_DEVICES = """
import jax, numpy as np
from repro.core import OrlojScheduler, SchedulerConfig
from repro.serving.engine import EngineConfig, ServingEngine
from test_engine import TINY

devs = jax.devices()
assert len(devs) == 2, devs
engine = ServingEngine(TINY, EngineConfig(buckets=(16, 32), batch_sizes=(1, 2), profile_reps=1))
lm = engine.profile_latency_model()
reqs, _ = engine.make_requests(
    16, lm, length_sampler=lambda rng: int(rng.integers(4, 32)),
    slo_scale=50.0, utilization=1.0, seed=3,
)
execs = [engine.executor_for(device=d) for d in devs]
scheds = [OrlojScheduler(lm, cfg=SchedulerConfig(batch_sizes=(1, 2))) for _ in devs]
res = engine.serve_pool(reqs, scheds, executors=execs)
assert res.n_total == 16 and res.conserved, res.summary()
batches = [len(ex.drain_measured()) for ex in execs]
assert sum(batches) == res.n_batches and min(batches) > 0, batches
"""


def test_serve_pool_one_replica_per_device():
    """Each replica of a pool runs its batches on its own device (two
    virtual CPU devices, in a child process so the flag takes effect)."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "PYTHONPATH": os.pathsep.join([str(Path(__file__).parent), *sys.path]),
    }
    subprocess.run(
        [sys.executable, "-c", POOL_ON_DEVICES], check=True, env=env, timeout=300
    )


def test_serve_real_requests_end_to_end(engine):
    lm = engine.profile_latency_model()
    reqs, hist = engine.make_requests(
        30,
        lm,
        length_sampler=lambda rng: int(rng.integers(4, 32)),
        slo_scale=50.0,  # generous: CPU timing jitter is large
        utilization=0.3,
        seed=1,
    )
    dists = {
        a: EmpiricalDistribution.from_samples(x)
        for a, x in hist.items()
        if len(x) >= 2
    }
    sched = OrlojScheduler(
        lm, cfg=SchedulerConfig(batch_sizes=(1, 2, 4)), initial_dists=dists
    )
    res = engine.serve(reqs, sched)
    assert res.n_total == 30
    assert res.n_finished_ok + res.n_finished_late + res.n_dropped == 30
    assert res.finish_rate > 0.5


# ---------------------------------------------------------------- decode path


def test_decode_executor_serves_token_requests(engine):
    """Continuous batching against the real decode-attention step: every
    request's tokens are served, slots recycle, and a second run on the
    same executor reuses the compiled step (slot reconciliation by rid)."""
    from repro.core.tokensched import FcfsTokenScheduler, TokenSchedConfig

    dec = engine.decode_executor(max_batch=4, max_cache=64)
    step_ms = dec.calibrate()
    assert step_ms > 0.0
    reqs = engine.make_token_requests(
        24, dec, mean_out=8.0, utilization=0.5, seed=2
    )
    cfg = TokenSchedConfig(
        max_batch=4,
        ttft_slo_ms=reqs[0].slo,  # generous: CPU timing jitter is large
        tpot_slo_ms=4.0 * step_ms,
        d0=step_ms,
        d1=0.0,
    )
    res = engine.serve_tokens(reqs, FcfsTokenScheduler(cfg), dec)
    assert res.n_total == 24 and res.conserved
    assert all(r.tokens_done == r.out_tokens for r in reqs)
    assert all(r.first_token is not None for r in reqs)
    # slots of the final step's finishers are reclaimed lazily on the next
    # run's first step — a fresh serve must start from full capacity
    reqs2 = engine.make_token_requests(
        8, dec, mean_out=4.0, utilization=0.5, seed=3
    )
    res2 = engine.serve_tokens(reqs2, FcfsTokenScheduler(cfg), dec)
    assert res2.n_total == 8
    assert all(r.tokens_done == r.out_tokens for r in reqs2)


def test_serve_tokens_rejects_oversized_scheduler(engine):
    from repro.core.tokensched import FcfsTokenScheduler, TokenSchedConfig

    dec = engine.decode_executor(max_batch=2, max_cache=32)
    with pytest.raises(ValueError, match="cache slots"):
        engine.serve_tokens(
            [], FcfsTokenScheduler(TokenSchedConfig(max_batch=8)), dec
        )


def test_decode_lower_step_has_the_executor_shapes(engine):
    dec = engine.decode_executor(max_batch=2, max_cache=32)
    kc, vc, valid, out = dec.lower_step().out_info
    assert kc.shape == vc.shape == (2, dec.n_kv, 32, dec.head_dim)
    assert valid.shape == (2,)
    assert out.shape == (2, dec.n_heads, dec.head_dim)


def test_decode_executor_pallas_interpreter_agrees(engine):
    """One measured step under the Pallas interpreter matches the jnp
    reference numerics bit-for-bit from identical seeded state — the
    kernel-integration check (auto-detect picks the reference on CPU;
    forcing use_pallas=True exercises the interpreter)."""
    import jax.numpy as jnp

    outs = {}
    for use_pallas in (False, True):
        dec = engine.decode_executor(
            max_batch=2, max_cache=32, use_pallas=use_pallas, seed=7
        )
        dec._valid = jnp.array([5, 0], jnp.int32)  # one occupied, one empty
        dec._decode_once()
        outs[use_pallas] = (np.asarray(dec.last_out), np.asarray(dec._valid))
    np.testing.assert_array_equal(outs[False][1], outs[True][1])
    # occupied slot: same attention numerics through either path
    np.testing.assert_allclose(
        outs[True][0][0], outs[False][0][0], rtol=2e-5, atol=1e-6
    )
    # empty slot (valid_len == 0) must come back all-zero, not NaN — the
    # fully-masked-row regression both kernel paths now share
    np.testing.assert_array_equal(outs[True][0][1], np.zeros_like(outs[True][0][1]))
