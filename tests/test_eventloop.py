"""Tests for the unified multi-worker discrete-event engine."""

import dataclasses

import pytest

from repro.core import (
    BatchLatencyModel,
    ModelExecutor,
    OrlojScheduler,
    Worker,
    run_event_loop,
    simulate,
)
from repro.core.eventloop import DISPATCH_POLICIES
from repro.serving.trace import TraceConfig, generate_requests
from repro.serving.workload import bimodal

LM = BatchLatencyModel(c0=25.0, c1=1.0)
SLOW_LM = BatchLatencyModel(c0=50.0, c1=2.0)

ALL_POLICIES = tuple(DISPATCH_POLICIES)  # every registered dispatch policy


def _rs(util, n=500, seed=11):
    return generate_requests(
        bimodal(1.0), LM, slo_scale=3.0,
        cfg=TraceConfig(n_requests=n, seed=seed, utilization=util),
    )


def _orloj(rs, lm=LM):
    return OrlojScheduler(lm, initial_dists=rs.initial_dists())


# ------------------------------------------------- single-worker equivalence
def test_one_worker_reproduces_simulate_bitwise():
    """The unified engine at n_workers=1 is *the* §5 harness: identical
    counts and bit-identical latencies to ``simulate`` on a seeded trace
    (jittered executor included — same seed, same draws)."""
    rs = _rs(util=0.9)
    a = simulate(
        rs.fresh(), _orloj(rs), ModelExecutor(LM, jitter=0.05, seed=3)
    )
    b = run_event_loop(
        rs.fresh(),
        [Worker(_orloj(rs), ModelExecutor(LM, jitter=0.05, seed=3))],
        policy="round_robin",
    )
    for f in (
        "n_total",
        "n_finished_ok",
        "n_finished_late",
        "n_dropped",
        "n_unserved",
        "worker_busy",
        "makespan_ms",
        "n_workers",
        "peak_heap_size",
    ):
        assert getattr(a, f) == getattr(b, f), f
    assert a.latencies.shape == b.latencies.shape
    assert a.latencies.tobytes() == b.latencies.tobytes()  # bit-for-bit
    assert a.n_workers == 1


@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_policy_choice_is_noop_for_one_worker(policy):
    rs = _rs(util=0.8, n=200)
    res = run_event_loop(
        rs.fresh(), [Worker(_orloj(rs), ModelExecutor(LM))], policy=policy
    )
    assert res.n_unserved == 0
    assert res.utilization <= 1.0 + 1e-9


# ------------------------------------------------------------ wake dedup
def test_event_heap_stays_bounded():
    """Per-worker wake dedup: at most one *live* WAKE per worker (plus any
    superseded earlier-re-armed wakes still waiting to fire as no-ops).
    On this seeded light-load trace the high-water mark stays within
    n_requests + 2·n_workers; the pre-unification cluster loop pushed a
    wake on *every* idle dispatch attempt and flooded far past that."""
    n, k = 400, 4
    rs = _rs(util=0.10, n=n)  # light load: workers mostly idle → wake-heavy
    for policy in ALL_POLICIES:
        res = run_event_loop(
            rs.fresh(),
            [Worker(_orloj(rs), ModelExecutor(LM)) for _ in range(k)],
            policy=policy,
        )
        assert res.peak_heap_size <= n + 2 * k, policy
        assert res.n_unserved == 0


# ------------------------------------------------- pool accounting honesty
def test_makespan_and_utilization_are_honest():
    """makespan is the virtual clock of the last event (not multiplied by
    the pool size), n_workers is explicit, and pool utilization is
    worker_busy / (makespan · n_workers) ≤ 1."""
    rs = _rs(util=2.0)
    one = simulate(rs.fresh(), _orloj(rs), ModelExecutor(LM))
    pool = run_event_loop(
        rs.fresh(),
        [Worker(_orloj(rs), ModelExecutor(LM)) for _ in range(3)],
        policy="least_loaded",
    )
    assert pool.n_workers == 3
    # same trace: the pool's clock ends within ~one batch of the
    # single-worker clock, nowhere near 3× (the old makespan=last·n hack)
    assert pool.makespan_ms < 1.5 * one.makespan_ms
    assert pool.worker_busy <= pool.makespan_ms * pool.n_workers + 1e-9
    assert pool.utilization <= 1.0 + 1e-9
    # a 3-replica pool at 2× one-worker load must beat the single worker
    assert pool.finish_rate > one.finish_rate


# ----------------------------------------------- heterogeneous replicas
def test_heterogeneous_pool_all_policies():
    """4 replicas, two fast + two slow (different executors AND different
    latency models per scheduler): completes under every dispatch policy
    with bounded heap and honest utilization."""
    n = 500
    rs = _rs(util=1.8, n=n)
    for policy in ALL_POLICIES:
        workers = []
        for i in range(4):
            lm = LM if i < 2 else SLOW_LM
            workers.append(
                Worker(_orloj(rs, lm=lm), ModelExecutor(lm, seed=i))
            )
        res = run_event_loop(rs.fresh(), workers, policy=policy, seed=7)
        assert res.n_workers == 4
        assert (
            res.n_finished_ok + res.n_finished_late + res.n_dropped
            + res.n_unserved == n
        ), policy
        assert res.utilization <= 1.0 + 1e-9, policy
        assert res.peak_heap_size <= n + 2 * 4, policy
        assert res.finish_rate > 0.4, policy


def test_p2c_tracks_jsq_under_load():
    """Two load probes per arrival should get within striking distance of
    the full-information work-queue balancer."""
    rs = _rs(util=1.6, n=600, seed=23)

    def run(policy):
        return run_event_loop(
            rs.fresh(),
            [Worker(_orloj(rs), ModelExecutor(LM)) for _ in range(4)],
            policy=policy,
            seed=1,
        ).finish_rate

    assert run("p2c") > run("jsq_work") - 0.15


# -------------------------------------------------- horizon & overhead
def test_horizon_truncates_pool_run():
    rs = _rs(util=1.0, n=300)
    res = run_event_loop(
        rs.fresh(),
        [Worker(_orloj(rs), ModelExecutor(LM)) for _ in range(2)],
        horizon=1.0,  # ms: essentially nothing finishes
    )
    assert res.n_unserved > 0
    # honest truncation: the clock reads the horizon, not the first event
    # beyond it, and busy time inside the window keeps utilization ≤ 1
    assert res.makespan_ms == 1.0
    assert 0.0 <= res.utilization <= 1.0 + 1e-9


def test_overhead_charging_completes():
    reqs = _rs(util=0.5, n=100)
    rs = reqs.fresh()
    res = run_event_loop(
        rs,
        [Worker(_orloj(reqs), ModelExecutor(LM))],
        charge_scheduler_overhead=True,
    )
    assert res.n_unserved == 0
    # charged overhead pushes every batch start strictly past its pop time
    assert all(r.started is None or r.started > r.release for r in rs)


# ------------------------------------------------------------- plumbing
def test_unknown_policy_rejected():
    rs = _rs(util=0.5, n=10)
    with pytest.raises(ValueError, match="unknown dispatch policy"):
        run_event_loop(
            rs.fresh(), [Worker(_orloj(rs), ModelExecutor(LM))], policy="nope"
        )
    with pytest.raises(ValueError, match="at least one worker"):
        run_event_loop(rs.fresh(), [])


def test_callable_policy():
    rs = _rs(util=1.0, n=200)
    res = run_event_loop(
        rs.fresh(),
        [Worker(_orloj(rs), ModelExecutor(LM)) for _ in range(2)],
        policy=lambda req, now, pool: req.rid % 2,
    )
    assert res.n_unserved == 0
    assert res.n_workers == 2


# ------------------------------------------------- array-engine contract
# The array engine (RequestStore + EventWheel, DESIGN.md §10) must be
# observably identical to the scalar oracle loop: same counts, same
# bit-level latencies, same per-object bookkeeping.  peak_heap_size is
# the one engine-specific field (both report peak pending events, but
# the scalar heap counts superseded-wake tombstones slightly
# differently), so it is bound-checked, not equality-checked.

_STABLE_FIELDS = (
    "n_total", "n_finished_ok", "n_finished_late", "n_dropped",
    "n_unserved", "worker_busy", "makespan_ms", "n_workers",
    "n_decisions", "n_batches",
)


def _run_both(rs, n_workers=1, policy="round_robin", **kw):
    out = {}
    for engine in ("scalar", "array"):
        reqs = rs.fresh()
        workers = [
            Worker(_orloj(rs), ModelExecutor(LM, seed=i))
            for i in range(n_workers)
        ]
        out[engine] = (
            run_event_loop(reqs, workers, policy=policy, engine=engine, **kw),
            reqs,
        )
    return out


@pytest.mark.parametrize("n_workers,policy", [(1, "round_robin"), (3, "p2c")])
def test_array_engine_bitwise_equivalent(n_workers, policy):
    rs = _rs(util=0.9 * n_workers)
    both = _run_both(rs, n_workers=n_workers, policy=policy)
    a, a_reqs = both["scalar"]
    b, b_reqs = both["array"]
    for f in _STABLE_FIELDS:
        assert getattr(a, f) == getattr(b, f), f
    assert a.latencies.tobytes() == b.latencies.tobytes()
    # identical per-object bookkeeping (the writeback contract)
    sa = sorted(a_reqs, key=lambda r: r.rid)
    sb = sorted(b_reqs, key=lambda r: r.rid)
    assert [(r.started, r.finished, r.dropped) for r in sa] == [
        (r.started, r.finished, r.dropped) for r in sb
    ]
    assert b.peak_heap_size <= a.peak_heap_size


def test_array_engine_with_quantized_trace():
    """Tick-quantized arrivals (the fleet grids' shape) exercise the
    coalesced same-timestamp bulk paths on both engines."""
    rs = generate_requests(
        bimodal(1.0), LM, slo_scale=3.0,
        cfg=TraceConfig(n_requests=400, seed=7, utilization=0.9, tick_ms=4.0),
    )
    both = _run_both(rs)
    a, _ = both["scalar"]
    b, _ = both["array"]
    for f in _STABLE_FIELDS:
        assert getattr(a, f) == getattr(b, f), f
    assert a.latencies.tobytes() == b.latencies.tobytes()


def test_array_engine_horizon_and_simulate_entry():
    rs = _rs(util=1.0, n=300)
    res = simulate(
        rs.fresh(), _orloj(rs), ModelExecutor(LM), horizon=1.0, engine="array"
    )
    assert res.makespan_ms == 1.0
    assert res.n_unserved > 0


def test_unknown_engine_rejected():
    rs = _rs(util=0.5, n=10)
    with pytest.raises(ValueError, match="unknown engine"):
        run_event_loop(
            rs.fresh(), [Worker(_orloj(rs), ModelExecutor(LM))], engine="simd"
        )


def test_batch_rows_columnar_scheduler_path():
    """A scheduler speaking the columnar protocol (on_arrival_row /
    on_arrivals_cols, Batch.rows ranges) matches an object-path scheduler
    making the same FIFO decisions — the engine's slice fast paths write
    the same columns the fancy-index fallback does."""
    from benchmarks.queue_micro import (
        _ConstExecutor,
        _eventloop_requests,
        _FifoColsScheduler,
        _FifoObjScheduler,
    )

    master = _eventloop_requests(2_000, tick_ms=4.0, rate_per_ms=64.0)

    def clone():
        return [
            type(r)(app_id=r.app_id, release=r.release, slo=r.slo,
                    true_time=r.true_time)
            for r in master
        ]

    a = run_event_loop(
        clone(), [Worker(_FifoObjScheduler(), _ConstExecutor())], engine="scalar"
    )
    b = run_event_loop(
        clone(), [Worker(_FifoColsScheduler(), _ConstExecutor())], engine="array"
    )
    for f in _STABLE_FIELDS:
        assert getattr(a, f) == getattr(b, f), f
    assert a.latencies.tobytes() == b.latencies.tobytes()


class _RecordingScheduler:
    """Shaped like the chip benchmark's pacing wrapper: the object hooks
    are its own, and every other attribute is forwarded to the wrapped
    scheduler through ``__getattr__`` (recorded when it is called)."""

    def __init__(self, inner):
        self.inner = inner
        self.delivered = 0  # arrivals that went through the object hooks
        self.forwarded: list[str] = []

    def __getattr__(self, name):
        attr = getattr(self.inner, name)
        if not callable(attr):
            return attr

        def call(*args, **kwargs):
            self.forwarded.append(name)
            return attr(*args, **kwargs)

        return call

    def on_arrival(self, req, now):
        self.delivered += 1
        self.inner.on_arrival(req, now)

    def on_arrivals(self, reqs, now):
        self.delivered += len(reqs)
        self.inner.on_arrivals(reqs, now)

    def next_batch(self, now):
        return self.inner.next_batch(now)

    def on_batch_done(self, batch, now, alone_times_ms):
        self.inner.on_batch_done(batch, now, alone_times_ms)


@pytest.mark.parametrize("n_workers", [1, 2])
def test_scalar_engine_delivers_arrivals_through_object_hooks_only(n_workers):
    """The scalar engine hands every arrival to a wrapping scheduler
    through ``on_arrival``/``on_arrivals``, never through a columnar hook
    the wrapper would forward unwrapped; the array engine, which may use
    those hooks, gives the same result on the same trace."""
    rs = generate_requests(
        bimodal(1.0), LM, slo_scale=3.0,
        cfg=TraceConfig(n_requests=400, seed=5, utilization=0.9 * n_workers,
                        tick_ms=4.0),
    )
    out = {}
    for engine in ("scalar", "array"):
        scheds = [_RecordingScheduler(_orloj(rs)) for _ in range(n_workers)]
        res = run_event_loop(
            rs.fresh(),
            [Worker(s, ModelExecutor(LM, seed=i)) for i, s in enumerate(scheds)],
            policy="least_loaded",
            engine=engine,
        )
        out[engine] = (res, scheds)
    a, scalar_scheds = out["scalar"]
    b, array_scheds = out["array"]
    assert sum(s.delivered for s in scalar_scheds) == a.n_total
    columnar = {"on_arrivals_cols", "on_arrival_row"}
    assert not any(columnar & set(s.forwarded) for s in scalar_scheds)
    if n_workers == 1:
        # the array engine does reach past the wrapper: the hazard the
        # scalar engine avoids
        assert "on_arrivals_cols" in array_scheds[0].forwarded
    for f in dataclasses.fields(a):
        if f.name in ("latencies", "sched_time_ms", "peak_heap_size"):
            continue  # wall-clock meter; the one engine-specific field
        assert getattr(a, f.name) == getattr(b, f.name), f.name
    assert a.latencies.tobytes() == b.latencies.tobytes()
    assert b.peak_heap_size <= a.peak_heap_size
