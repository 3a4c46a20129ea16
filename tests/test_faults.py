"""Tests for the fault-injection tier (``repro.serving.faults``,
DESIGN.md §11): plan validation and determinism, engine bit-identity
under active plans, the fault-free no-op guarantee, admission control,
the deadline-aware retry gate, wall-budget truncation, fleet re-dispatch
and the hard conservation invariant — property-tested across engines."""

import dataclasses

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    BatchLatencyModel,
    ModelExecutor,
    OrlojScheduler,
    Worker,
    run_event_loop,
)
from repro.serving import FaultPlan, finish_probability
from repro.serving.cluster import run_fleet
from repro.serving.trace import TraceConfig, generate_requests
from repro.serving.workload import bimodal

LM = BatchLatencyModel(c0=25.0, c1=1.0)

_COUNT_FIELDS = (
    "n_total",
    "n_finished_ok",
    "n_finished_late",
    "n_dropped",
    "n_unserved",
    "n_rejected",
    "n_failed",
    "n_retried",
    "n_batches",
    "n_workers",
    "truncated",
)


def _rs(util=1.2, n=400, seed=11, slo=2.0):
    return generate_requests(
        bimodal(1.0), LM, slo_scale=slo,
        cfg=TraceConfig(n_requests=n, seed=seed, utilization=util),
    )


def _workers(rs, k=1):
    return [
        Worker(OrlojScheduler(LM, initial_dists=rs.initial_dists()),
               ModelExecutor(LM))
        for _ in range(k)
    ]


def _assert_identical(a, b):
    for f in _COUNT_FIELDS:
        assert getattr(a, f) == getattr(b, f), f
    assert a.latencies.tobytes() == b.latencies.tobytes()


CHAOS = FaultPlan(
    seed=5, mttf_ms=3_000.0, restart_delay_ms=100.0, max_retries=3,
    retry_backoff_ms=10.0, retry_threshold=0.05, straggler_prob=0.1,
    straggler_factor=2.5, admission_floor=0.05,
)


# --------------------------------------------------------------- FaultPlan
def test_plan_validation():
    with pytest.raises(ValueError):
        FaultPlan(mttf_ms=-1.0)
    with pytest.raises(ValueError):
        FaultPlan(straggler_prob=1.5)
    with pytest.raises(ValueError):
        FaultPlan(straggler_prob=0.5, straggler_factor=0.5)
    with pytest.raises(ValueError):
        FaultPlan(admission_floor=2.0)
    with pytest.raises(ValueError):
        FaultPlan(max_retries=-1)
    with pytest.raises(ValueError):
        FaultPlan(batch_timeout_ms=-0.5)


def test_plan_enabled_and_dict_round_trip():
    assert not FaultPlan().enabled()
    assert not FaultPlan(seed=9, max_retries=5, retry_backoff_ms=3.0).enabled()
    for kw in (
        {"mttf_ms": 1.0},
        {"straggler_prob": 0.1, "straggler_factor": 2.0},
        {"admission_floor": 0.1},
        {"batch_timeout_ms": 50.0},
    ):
        assert FaultPlan(**kw).enabled(), kw
    assert FaultPlan.from_dict(CHAOS.to_dict()) == CHAOS
    # unknown keys (future knobs in old artifacts) are ignored, not fatal
    assert FaultPlan.from_dict({"mttf_ms": 2.0, "not_a_knob": 1}) == FaultPlan(
        mttf_ms=2.0
    )


def test_same_seed_same_draws():
    """Two FaultStates from one plan replay identical crash renewals and
    straggler draws; a different seed diverges."""
    a, b = CHAOS.start(4), CHAOS.start(4)
    for w in range(4):
        assert [a.next_crash(w, 0.0) for _ in range(20)] == [
            b.next_crash(w, 0.0) for _ in range(20)
        ]
    durs = np.linspace(10.0, 200.0, 50)
    assert [a.straggle(d) for d in durs] == [b.straggle(d) for d in durs]
    c = dataclasses.replace(CHAOS, seed=6).start(4)
    assert [a.next_crash(0, 0.0) for _ in range(20)] != [
        c.next_crash(0, 0.0) for _ in range(20)
    ]


def test_crash_streams_are_per_worker():
    """Worker w's renewal sequence does not depend on how often other
    workers' streams are consumed — the engine-invariance keystone."""
    a = CHAOS.start(3)
    b = CHAOS.start(3)
    for _ in range(10):
        b.next_crash(0, 0.0)  # burn worker 0's stream only
    assert [a.next_crash(2, 0.0) for _ in range(5)] == [
        b.next_crash(2, 0.0) for _ in range(5)
    ]


# --------------------------------------------------- finish prob / retry
def test_finish_probability_edges():
    rs = _rs(n=50)
    sched = OrlojScheduler(LM, initial_dists=rs.initial_dists())
    req = rs.fresh()[0]
    assert finish_probability(sched, req, req.deadline + 1.0) == 0.0
    p = finish_probability(sched, req, req.release)
    assert 0.0 <= p <= 1.0

    class _Blind:  # no latency knowledge at all: optimistic no-op gate
        pass

    assert finish_probability(_Blind(), req, req.release) == 1.0


def test_retry_gate_exhaustion_and_deadline():
    rs = _rs(n=50)
    sched = OrlojScheduler(LM, initial_dists=rs.initial_dists())
    state = FaultPlan(seed=1, max_retries=1, retry_backoff_ms=5.0).start(1)
    req = rs.fresh()[0]
    req.retries = 0
    ok, t_retry = state.retry_decision(sched, req, req.release)
    assert ok and t_retry >= req.release
    req.retries = 1  # budget exhausted
    assert state.retry_decision(sched, req, req.release)[0] is False
    req.retries = 0  # past the deadline: probability floor kills it
    assert state.retry_decision(sched, req, req.deadline + 1.0)[0] is False


# ------------------------------------------------------ fault-free no-op
@pytest.mark.parametrize("engine", ["scalar", "array"])
def test_disabled_plan_is_bitwise_noop(engine):
    """faults=None, faults={} at the spec level and a populated-but-
    disabled plan all produce bit-identical results: threading the hook
    points costs nothing observable."""
    rs = _rs()
    bare = run_event_loop(rs.fresh(), _workers(rs, 2), seed=3, engine=engine)
    disabled = run_event_loop(
        rs.fresh(), _workers(rs, 2), seed=3, engine=engine,
        faults=FaultPlan(seed=99, max_retries=7, retry_backoff_ms=50.0),
    )
    _assert_identical(bare, disabled)
    assert disabled.n_rejected == disabled.n_failed == disabled.n_retried == 0


# ------------------------------------------------- engine equivalence
@pytest.mark.parametrize("k", [1, 4])
def test_scalar_array_identical_under_chaos(k):
    """The bit-identity equivalence claim extends to every FaultPlan:
    crashes + stragglers + admission + retries, one and many workers."""
    rs = _rs(n=500)
    a = run_event_loop(
        rs.fresh(), _workers(rs, k), policy="least_loaded", seed=7,
        engine="scalar", faults=CHAOS,
    )
    b = run_event_loop(
        rs.fresh(), _workers(rs, k), policy="least_loaded", seed=7,
        engine="array", faults=CHAOS,
    )
    _assert_identical(a, b)
    assert a.conserved
    assert a.n_retried > 0  # the plan actually fired


def test_batch_timeout_abort_path():
    """batch_timeout_ms aborts slow batches on both engines identically;
    timed-out requests end as retried-then-resolved or failed, never
    lost."""
    rs = _rs(n=300)
    plan = FaultPlan(seed=2, batch_timeout_ms=60.0, max_retries=1,
                     retry_backoff_ms=5.0)
    a = run_event_loop(rs.fresh(), _workers(rs, 2), seed=5,
                       engine="scalar", faults=plan)
    b = run_event_loop(rs.fresh(), _workers(rs, 2), seed=5,
                       engine="array", faults=plan)
    _assert_identical(a, b)
    assert a.conserved
    assert a.n_retried + a.n_failed > 0


# ------------------------------------------------------ admission control
def test_admission_floor_rejects_under_overload():
    rs = _rs(util=3.0, n=400)
    plan = FaultPlan(seed=3, admission_floor=0.4)
    res = {
        e: run_event_loop(rs.fresh(), _workers(rs), seed=9, engine=e,
                          faults=plan)
        for e in ("scalar", "array")
    }
    _assert_identical(res["scalar"], res["array"])
    r = res["scalar"]
    assert r.n_rejected > 0
    assert r.conserved
    # rejected requests never execute: no latency sample for them
    assert len(r.latencies) == r.n_finished_ok + r.n_finished_late


# ----------------------------------------------------------- truncation
@pytest.mark.parametrize("engine", ["scalar", "array"])
def test_wall_budget_truncates_gracefully(engine):
    rs = _rs(n=2_000)
    res = run_event_loop(
        rs.fresh(), _workers(rs, 2), seed=1, engine=engine,
        faults=CHAOS, wall_budget_s=1e-9,
    )
    assert res.truncated
    assert res.conserved
    assert res.n_unserved > 0  # cut off early: unresolved work is visible
    assert res.worker_busy <= res.makespan_ms * res.n_workers + 1e-9


# ----------------------------------------------------------- fleet mode
def test_fleet_chaos_equivalence_and_conservation():
    rs = _rs(n=600, util=1.5)
    kw = dict(n_pools=2, inter="p2c", intra="round_robin", seed=7,
              faults=CHAOS)
    a = run_fleet(rs.fresh(), _workers(rs, 6), engine="scalar", **kw)
    b = run_fleet(rs.fresh(), _workers(rs, 6), engine="array", **kw)
    _assert_identical(a, b)
    assert a.conserved
    assert a.n_retried > 0


@pytest.mark.parametrize("engine", ["scalar", "array"])
def test_dead_target_retries_drain_to_sibling(engine):
    """Requeued work targeted at a dead worker re-routes to a live
    sibling (the fleet drain path).  All arrivals pin to worker 0, which
    crashes early and stays down for the rest of the run; with a sibling
    present the aborted requests finish on it, alone they stall until
    the far restart and die late."""
    # seed 161: worker 0's first crash lands at ~1.9s (mid-batch under
    # 2x overload), worker 1's not before ~9.3s — a live sibling window
    plan = FaultPlan(
        seed=161, mttf_ms=1_500.0, restart_delay_ms=1e6,  # die, stay dead
        max_retries=3, retry_backoff_ms=1.0,
    )
    pin0 = lambda req, now, pool: 0  # noqa: E731

    def run(k):
        rs = _rs(n=200, util=2.0, seed=17)
        return run_event_loop(
            rs.fresh(), _workers(rs, k), policy=pin0, seed=3,
            engine=engine, faults=plan,
        )

    alone, paired = run(1), run(2)
    assert alone.conserved and paired.conserved
    assert paired.n_retried > 0  # the crash aborted in-flight work
    # worker 0's crash stream is seeded identically in both runs; only
    # the sibling explains the recovered finishes
    assert paired.n_finished_ok > alone.n_finished_ok


# ------------------------------------------------- conservation property
def _conservation_case(seed, util, k, level, engine):
    rs = _rs(util=util, n=200, seed=seed)
    plan = FaultPlan(
        seed=seed, mttf_ms=800.0 * level, restart_delay_ms=50.0,
        max_retries=2, retry_backoff_ms=5.0, retry_threshold=0.05,
        straggler_prob=0.1, straggler_factor=2.0, admission_floor=0.05,
    )
    res = run_event_loop(
        rs.fresh(), _workers(rs, k), policy="least_loaded", seed=seed,
        engine=engine, faults=plan,
    )
    assert res.conserved, (seed, util, k, level, engine)
    assert res.n_finished_ok + res.n_finished_late == len(res.latencies)


@pytest.mark.parametrize("engine", ["scalar", "array"])
@pytest.mark.parametrize("seed,util,k,level", [
    (0, 0.5, 1, 1.0),
    (1, 1.5, 2, 0.25),
    (2, 3.0, 3, 4.0),
    (3, 1.0, 4, 0.5),
])
def test_conservation_examples(engine, seed, util, k, level):
    """Seeded example grid of the conservation invariant — always runs,
    hypothesis or not."""
    _conservation_case(seed, util, k, level, engine)


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    util=st.floats(min_value=0.2, max_value=4.0),
    k=st.integers(min_value=1, max_value=4),
    level=st.floats(min_value=0.1, max_value=8.0),
    engine=st.sampled_from(["scalar", "array"]),
)
def test_conservation_property(seed, util, k, level, engine):
    """Every request reaches exactly one terminal state (or none —
    unserved) under arbitrary seeded fault plans, on both engines."""
    _conservation_case(seed, util, k, level, engine)
