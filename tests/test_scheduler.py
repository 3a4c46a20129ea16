"""Tests for the ORLOJ scheduler (Algorithm 1) and the simulator."""

import numpy as np
import pytest

from repro.core import (
    Batch,
    BatchLatencyModel,
    ClipperScheduler,
    ClockworkScheduler,
    EDFScheduler,
    EmpiricalDistribution,
    ModelExecutor,
    NexusScheduler,
    OrlojScheduler,
    Request,
    SchedulerConfig,
    simulate,
)
from repro.serving.trace import TraceConfig, generate_requests
from repro.serving.workload import bimodal, k_modal, static

LM = BatchLatencyModel(c0=25.0, c1=1.0)


def _dists():
    return {
        "a": EmpiricalDistribution(np.array([10.0, 30.0]), np.array([1.0])),
        "b": EmpiricalDistribution(np.array([80.0, 120.0]), np.array([1.0])),
    }


def _sched(**kw):
    return OrlojScheduler(LM, initial_dists=_dists(), **kw)


def test_single_request_served():
    s = _sched()
    r = Request(app_id="a", release=0.0, slo=500.0, true_time=20.0)
    s.on_arrival(r, 0.0)
    batch, _ = s.next_batch(0.0)
    assert batch is not None and batch.requests == [r]
    assert s.n_pending == 0


def test_batch_formed_from_pending():
    s = _sched()
    reqs = [
        Request(app_id="a", release=0.0, slo=2_000.0, true_time=20.0)
        for _ in range(16)
    ]
    for r in reqs:
        s.on_arrival(r, 0.0)
    batch, _ = s.next_batch(0.0)
    assert batch is not None
    assert len(batch) == batch.batch_size
    assert len(batch) > 1  # plenty of slack: should batch


def test_hopeless_request_dropped():
    s = _sched()
    r = Request(app_id="b", release=0.0, slo=5.0, true_time=100.0)  # impossible
    s.on_arrival(r, 0.0)
    batch, _ = s.next_batch(0.0)
    assert batch is None
    assert r.dropped is not None
    assert s.n_timed_out == 1


def test_deadline_pressure_serves_urgent_in_time():
    """An urgent request among slack ones is served before its deadline.
    (Note: Eq. 2 is *not* strict EDF — a nearly-hopeless request loses
    priority, Fig. 6c — so we assert end-to-end behaviour, not the exact
    batch membership at one instant.)"""
    urgent = Request(app_id="a", release=0.0, slo=180.0, true_time=20.0)
    laters = [
        Request(app_id="a", release=0.0, slo=5_000.0, true_time=20.0)
        for _ in range(6)
    ]
    res = simulate(
        [urgent] + laters, _sched(), ModelExecutor(LM)
    )
    assert urgent.ok
    assert res.n_finished_ok == 7


def test_base_time_reset_keeps_working():
    s = _sched()
    # Drive the clock far enough that b·t would overflow without resets.
    t = 0.0
    served = 0
    for i in range(40):
        t = i * 40_000.0  # 40 s steps → b·t up to 160 ≫ RESET_EXPONENT
        r = Request(app_id="a", release=t, slo=1_000.0, true_time=20.0)
        s.on_arrival(r, t)
        batch, _ = s.next_batch(t)
        if batch:
            served += len(batch)
    assert served == 40  # nothing lost to overflow


def test_milestone_updates_change_selection():
    """As deadlines pass milestones, stale requests decay to zero priority
    and the drop phase removes them."""
    s = _sched()
    r = Request(app_id="a", release=0.0, slo=140.0, true_time=20.0)
    s.on_arrival(r, 0.0)
    # Let its deadline pass without dispatching.
    batch, _ = s.next_batch(139.0)
    # r is infeasible at every batch size by now (est ≥ c0+c1·E[l] > 1ms).
    assert batch is None or r not in batch.requests


def test_paper_desc_ordering_runs():
    s = OrlojScheduler(
        LM,
        cfg=SchedulerConfig(bs_order="paper_desc"),
        initial_dists=_dists(),
    )
    for i in range(8):
        s.on_arrival(
            Request(app_id="a", release=0.0, slo=3_000.0, true_time=20.0), 0.0
        )
    batch, _ = s.next_batch(0.0)
    assert batch is not None


def test_scheduler_end_to_end_finishes_requests():
    rs = generate_requests(
        bimodal(1.0), LM, slo_scale=3.0, cfg=TraceConfig(n_requests=300, seed=0)
    )
    sched = OrlojScheduler(LM, initial_dists=rs.initial_dists())
    res = simulate(rs.fresh(), sched, ModelExecutor(LM))
    assert res.n_total == 300
    assert res.finish_rate > 0.7
    # conservation: every request is accounted for exactly once
    assert (
        res.n_finished_ok + res.n_finished_late + res.n_dropped + res.n_unserved
        == res.n_total
    )


@pytest.mark.parametrize(
    "mk",
    [
        lambda warm: ClockworkScheduler(LM, init_samples=warm),
        lambda warm: ClockworkScheduler(LM, init_samples=warm, adaptive=True),
        lambda warm: NexusScheduler(LM, init_samples=warm),
        lambda warm: ClipperScheduler(LM, init_samples=warm),
        lambda warm: EDFScheduler(LM, init_samples=warm),
    ],
)
def test_baselines_end_to_end(mk):
    rs = generate_requests(
        bimodal(1.0), LM, slo_scale=3.0, cfg=TraceConfig(n_requests=300, seed=0)
    )
    warm = np.concatenate(list(rs.app_history.values()))
    res = simulate(rs.fresh(), mk(warm), ModelExecutor(LM))
    assert res.n_total == 300
    assert res.finish_rate > 0.2
    assert (
        res.n_finished_ok + res.n_finished_late + res.n_dropped + res.n_unserved
        == res.n_total
    )


def test_orloj_beats_baselines_on_dynamic():
    """The paper's headline claim, at reduced scale (§5.3)."""
    rs = generate_requests(
        k_modal(3),
        LM,
        slo_scale=4.0,
        cfg=TraceConfig(n_requests=800, seed=2, utilization=0.85),
    )
    warm = np.concatenate(list(rs.app_history.values()))
    orloj = simulate(
        rs.fresh(), OrlojScheduler(LM, initial_dists=rs.initial_dists()),
        ModelExecutor(LM),
    ).finish_rate
    for mk in (NexusScheduler, ClipperScheduler):
        base = simulate(rs.fresh(), mk(LM, init_samples=warm), ModelExecutor(LM))
        assert orloj >= base.finish_rate - 0.02, mk.__name__
    cw = simulate(
        rs.fresh(), ClockworkScheduler(LM, init_samples=warm), ModelExecutor(LM)
    )
    assert orloj >= cw.finish_rate - 0.03


def test_orloj_comparable_on_static():
    """§5.4: no regression on static workloads."""
    rs = generate_requests(
        static(30.0),
        LM,
        slo_scale=4.0,
        cfg=TraceConfig(n_requests=600, seed=3, utilization=0.6),
    )
    warm = np.concatenate(list(rs.app_history.values()))
    orloj = simulate(
        rs.fresh(), OrlojScheduler(LM, initial_dists=rs.initial_dists()),
        ModelExecutor(LM),
    ).finish_rate
    cw = simulate(
        rs.fresh(), ClockworkScheduler(LM, init_samples=warm), ModelExecutor(LM)
    ).finish_rate
    assert orloj >= cw - 0.05


def test_profiler_feedback_loop_adapts():
    """Start with a wrong prior; the online profiler must correct it."""
    wrong = {
        "app0": EmpiricalDistribution(np.array([1.0, 2.0]), np.array([1.0])),
        "app1": EmpiricalDistribution(np.array([1.0, 2.0]), np.array([1.0])),
    }
    rs = generate_requests(
        bimodal(1.0), LM, slo_scale=4.0, cfg=TraceConfig(n_requests=600, seed=4)
    )
    sched = OrlojScheduler(LM, initial_dists=wrong)
    res = simulate(rs.fresh(), sched, ModelExecutor(LM))
    # the learned mixture must end up far from the wrong prior
    assert sched._mix.mean() > 10.0
    assert res.finish_rate > 0.5


@pytest.mark.parametrize("engine", ["scalar", "array"])
def test_stacked_scoring_keeps_every_decision(monkeypatch, engine):
    """Scoring arrivals at every batch size in one stacked pass changes no
    decision: on a virtual clock, the same bimodal trace gives the same
    batches in the same order, the same drops and the same ``n_scored`` as
    the per-batch-size ``score_many`` loop it replaced."""
    from repro.core import scheduler as sched_mod

    rs = generate_requests(
        bimodal(1.0), LM, slo_scale=2.0,
        cfg=TraceConfig(n_requests=1500, seed=5, utilization=0.95),
    )

    def run():
        reqs = rs.fresh()
        index = {r.rid: i for i, r in enumerate(reqs)}
        sched = OrlojScheduler(LM, initial_dists=rs.initial_dists())
        inner = ModelExecutor(LM)
        batches = []

        def execute(batch, now):
            batches.append(
                (now, batch.batch_size, [index[r.rid] for r in batch.requests])
            )
            return inner(batch, now)

        res = simulate(reqs, sched, execute, engine=engine)
        drops = [(i, r.dropped) for i, r in enumerate(reqs) if r.dropped is not None]
        return batches, drops, res.n_scored

    recomputes = []
    full = OrlojScheduler._recompute_all
    monkeypatch.setattr(
        OrlojScheduler, "_recompute_all",
        lambda self, now: recomputes.append(now) or full(self, now),
    )
    shipped = run()

    def per_batch_size(model, deadlines, costs, seg_starts, t, base):
        return [
            sched_mod._score_flat(m, deadlines, costs, seg_starts, t, base)
            for m in model.models
        ]

    monkeypatch.setattr(sched_mod, "_score_stacked", per_batch_size)
    looped = run()
    batches, drops, n_scored = shipped
    # a run that batches, sheds and swaps profiler snapshots (full recompute)
    assert len(batches) > 100 and drops and recomputes
    assert batches == looped[0]
    assert drops == looped[1]
    assert n_scored == looped[2]
