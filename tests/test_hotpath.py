"""Oracle/property tests for the vectorized scheduler hot path.

The invariants (DESIGN.md §Hot-path):

- ``BinScoreModel.score_many`` agrees *bit for bit* with the scalar
  ``score`` (which is a thin wrapper over it) and with the literal-Eq.-2
  ``value_reference`` oracle to float tolerance, across all three regimes
  and for piecewise-step costs;
- ``StackedScoreModel.score_many`` (every batch size in one pass) agrees
  bit for bit with each batch size's ``score_many``;
- ``HullQueue.insert_many`` / ``bulk_load`` produce an envelope identical
  to sequential ``insert``;
- ``OrlojScheduler.on_arrivals`` leaves the scheduler in the same state as
  the equivalent sequence of ``on_arrival`` calls.
"""

import math

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    BatchLatencyModel,
    EmpiricalDistribution,
    OrlojScheduler,
    Request,
)
from repro.core.hull import HullQueue
from repro.core.priority import DEFAULT_B, BinScoreModel, aggregate_steps

LM = BatchLatencyModel(c0=25.0, c1=1.0)


def _model(b=DEFAULT_B, edges=(20.0, 60.0, 120.0, 260.0), probs=(0.5, 0.3, 0.2)):
    d = EmpiricalDistribution(np.array(edges), np.array(probs))
    return BinScoreModel(d, b=b)


def _req(release=0.0, slo=500.0, cost=1.0, **kw):
    return Request(app_id="a", release=release, slo=slo, true_time=10.0,
                   cost=cost, **kw)


# --------------------------------------------------------------- score_many
def test_score_many_matches_scalar_bitwise_all_regimes():
    """One vectorized pass == N scalar scores, bit for bit, with t placed
    before / inside / after every milestone of every request."""
    m = _model()
    reqs = [_req(release=30.0 * i, slo=200.0 + 90.0 * i, cost=1.0 + 0.5 * i)
            for i in range(12)]
    deadlines = np.array([r.release + r.slo for r in reqs])
    costs = np.array([r.cost for r in reqs])
    # every milestone edge ± epsilon, plus far-before and far-after
    probes = [0.0, 5_000.0]
    for d in deadlines:
        for edge in np.concatenate([m.l1, m.l2]):
            for eps in (-1e-3, 0.0, 1e-3):
                probes.append(d - edge + eps)
    for t in probes:
        alpha, beta, miles = m.score_many(deadlines, costs, t, base=0.0)
        for i, r in enumerate(reqs):
            sc = m.score(r, t, base=0.0)
            assert sc.alpha == alpha[i], (t, i)
            assert sc.beta == beta[i], (t, i)
            assert sc.milestone == miles[i], (t, i)


def test_score_many_matches_literal_eq2_oracle():
    m = _model()
    reqs = [_req(release=17.0 * i, slo=150.0 + 123.0 * i) for i in range(8)]
    deadlines = np.array([r.release + r.slo for r in reqs])
    costs = np.array([r.cost for r in reqs])
    for t in np.linspace(0.0, 1_500.0, 61):
        alpha, beta, _ = m.score_many(deadlines, costs, t, base=0.0)
        x = math.exp(m.b * t)
        for i, r in enumerate(reqs):
            want = m.value_reference(r, t, base=0.0)
            got = alpha[i] * x + beta[i]
            assert np.isclose(got, want, rtol=1e-9, atol=1e-12), (t, i)


def test_score_many_piecewise_step_costs():
    """Appendix-B decomposition through the flat-step + aggregate path."""
    m = _model()
    multi = _req(slo=400.0, cost=1.0, extra_deadlines=((600.0, 3.0), (900.0, 4.5)))
    from repro.core.scheduler import _flatten_steps, _score_flat

    for t in (0.0, 150.0, 380.0, 450.0, 640.0, 880.0, 1_000.0):
        d, c, seg = _flatten_steps([multi, _req(slo=500.0)])
        assert seg is not None and list(seg) == [0, 3]
        alpha, beta, miles = _score_flat(m, d, c, seg, t, 0.0)
        sc = m.score(multi, t, 0.0)
        assert sc.alpha == alpha[0] and sc.beta == beta[0]
        assert sc.milestone == miles[0]
        assert np.isclose(
            alpha[0] * math.exp(m.b * t) + beta[0],
            m.value_reference(multi, t, 0.0),
            rtol=1e-9, atol=1e-12,
        )


def test_score_many_milestones_strictly_future():
    """A returned milestone is > t (up to one float rounding step, which the
    scheduler guards); at a milestone the folded (α, β) change."""
    m = _model()
    r = _req(slo=400.0)
    t = 0.0
    seen = 0
    while True:
        sc = m.score(r, t, 0.0)
        if not math.isfinite(sc.milestone):
            break
        assert sc.milestone > t
        nxt = m.score(r, sc.milestone, 0.0)
        assert (nxt.alpha, nxt.beta) != (sc.alpha, sc.beta)
        t = sc.milestone
        seen += 1
    # every distinct regime edge (D − l for each unique bin edge) visited
    assert seen == np.union1d(m.l1, m.l2).size


def test_milestones_never_dropped_with_fullmantissa_edges():
    """Regression: with profiler-derived bin edges (full float mantissas)
    the time-space milestone ``fl(D − l)`` can land exactly ON the wake
    time while the slack-space regime test has not flipped yet; the
    scheduler re-scores at exactly that instant (the WAKE path).  The
    returned next milestone must still be strictly future — a dropped one
    would leave the hull line stale until a base reset.  Walking every
    milestone at its exact float time must terminate with a ~zero score
    past the last regime edge."""
    rng = np.random.default_rng(42)
    for trial in range(50):
        samples = rng.lognormal(mean=3.0, sigma=0.7, size=64)
        d = EmpiricalDistribution.from_samples(samples, n_bins=12)
        m = BinScoreModel(d, b=DEFAULT_B)
        r = _req(release=float(rng.uniform(0, 1e6)),
                 slo=float(rng.uniform(200.0, 4_000.0)))
        t = r.release
        hops = 0
        while True:
            sc = m.score(r, t, base=r.release)
            if not math.isfinite(sc.milestone):
                break
            assert sc.milestone > t, (trial, t)
            t = sc.milestone  # re-score at the exact wake float
            hops += 1
            assert hops <= 2 * (len(m.l1) + len(m.l2)), trial
        # past the last edge the priority has decayed to (numerically) zero
        assert abs(m.value(r, t + 1e-6, r.release)) < 1e-9


@given(
    slo=st.floats(min_value=50.0, max_value=5_000.0),
    t=st.floats(min_value=0.0, max_value=5_000.0),
    base=st.floats(min_value=-1_000.0, max_value=1_000.0),
    cost=st.floats(min_value=0.1, max_value=50.0),
)
@settings(max_examples=80, deadline=None)
def test_property_score_many_equals_scalar(slo, t, base, cost):
    m = _model()
    r = _req(slo=slo, cost=cost)
    alpha, beta, miles = m.score_many(
        np.array([r.deadline]), np.array([cost]), t, base
    )
    sc = m.score(r, t, base)
    assert sc.alpha == alpha[0] and sc.beta == beta[0]
    assert sc.milestone == miles[0]
    assert np.isclose(
        sc.value(t, base, m.b), m.value_reference(r, t, base),
        rtol=1e-9, atol=1e-12,
    )


def test_aggregate_steps_segments():
    alpha = np.array([1.0, 2.0, 4.0, 8.0])
    beta = np.array([0.5, 0.25, 0.125, 0.0625])
    miles = np.array([9.0, 3.0, np.inf, 7.0])
    a, b, m = aggregate_steps(alpha, beta, miles, np.array([0, 2]))
    assert list(a) == [3.0, 12.0]
    assert list(b) == [0.75, 0.1875]
    assert list(m) == [3.0, 7.0]


# ---------------------------------------------------------- stacked scoring
def _random_models(rng, bin_counts):
    """One score model per batch size with profiler-like full-mantissa bin
    edges, ``bin_counts[k]`` bins each (uneven counts exercise the padding)."""
    models = []
    for n_bins in bin_counts:
        edges = np.sort(rng.uniform(0.3, 600.0, size=n_bins + 1))
        probs = rng.dirichlet(np.ones(n_bins))
        models.append(BinScoreModel(EmpiricalDistribution(edges, probs)))
    return models


def _assert_rows_bitwise(stacked, deadlines, costs, t, base):
    got = stacked.score_many(deadlines, costs, t, base)
    for k, m in enumerate(stacked.models):
        want = m.score_many(deadlines, costs, t, base)
        for g, w in zip(got, want):
            assert np.array_equal(g[k], w), (k, t, base)


@pytest.mark.parametrize("n", [1, 2, 37])
def test_stacked_score_many_equals_each_batch_size_bitwise(n):
    """Row k of the one-pass score equals batch size k's ``score_many``
    bit for bit, over random deadlines, costs, t and base, with
    histograms of different bin counts."""
    from repro.core.priority import StackedScoreModel

    rng = np.random.default_rng(n)
    for _ in range(40):
        stacked = StackedScoreModel(_random_models(rng, (24, 3, 12, 24, 7)))
        t = float(rng.uniform(0.0, 1e6))
        base = t - float(rng.uniform(0.0, 5e5))
        deadlines = t + rng.uniform(-50.0, 900.0, size=n)
        costs = rng.uniform(0.1, 50.0, size=n)
        _assert_rows_bitwise(stacked, deadlines, costs, t, base)


def test_stacked_score_many_slack_on_bin_edge():
    """Slack exactly on an edge, and the wake instant ``t = fl(D − edge)``
    where the naive next milestone lands on ``t`` itself (the ulp-coincident
    step of ``_next_future``): still bit for bit per batch size."""
    from repro.core.priority import StackedScoreModel

    rng = np.random.default_rng(7)
    ulp_cases = 0
    for _ in range(30):
        stacked = StackedScoreModel(_random_models(rng, (12, 24, 5)))
        edges = np.unique(np.concatenate([m.l1 for m in stacked.models]
                                         + [m.l2 for m in stacked.models]))
        # slack == edge exactly: t = 0, D = edge
        _assert_rows_bitwise(stacked, edges, np.ones_like(edges), 0.0, 0.0)
        d = float(rng.uniform(1e5, 1e6))
        for e in edges:
            t = d - e
            _assert_rows_bitwise(stacked, np.array([d, d + 1.0]),
                                 np.array([1.0, 2.0]), t, t - 7.0)
            for m in stacked.models:
                j = np.searchsorted(m.l1, d - t, side="left")
                ulp_cases += bool(j > 0 and d - m.l1[j - 1] <= t)
    assert ulp_cases > 0  # the stepping loop ran


def test_stacked_piecewise_steps_through_seg_starts():
    """Appendix-B requests fold back per batch size through
    ``aggregate_steps`` exactly as the per-batch-size path does."""
    from repro.core.scheduler import _flatten_steps, _score_flat, _score_stacked

    s = OrlojScheduler(LM, initial_dists=_dists())
    reqs = [
        _req(slo=400.0, cost=1.0, extra_deadlines=((600.0, 3.0), (900.0, 4.5))),
        _req(slo=500.0),
        _req(release=20.0, slo=250.0, cost=2.0, extra_deadlines=((700.0, 2.5),)),
    ]
    d, c, seg = _flatten_steps(reqs)
    assert seg is not None
    for t in (0.0, 150.0, 380.0, 450.0, 640.0, 880.0, 1_000.0):
        rows = _score_stacked(s._stacked, d, c, seg, t, 0.0)
        assert len(rows) == len(s._bs_state)
        for row, st_ in zip(rows, s._bs_state.values()):
            want = _score_flat(st_.score_model, d, c, seg, t, 0.0)
            for g, w in zip(row, want):
                assert np.array_equal(g, w), t


def test_stacked_score_model_rejects_mixed_b():
    from repro.core.priority import StackedScoreModel

    d = EmpiricalDistribution(np.array([1.0, 2.0]), np.array([1.0]))
    with pytest.raises(ValueError):
        StackedScoreModel([BinScoreModel(d, b=1e-4), BinScoreModel(d, b=2e-4)])


# ---------------------------------------------------------------- bulk hull
def _envelope(q: HullQueue, xs) -> list:
    return [q.argmax(float(x)) for x in xs]


def test_bulk_load_envelope_matches_sequential_insert():
    rng = np.random.default_rng(5)
    lines = [(i, float(a), float(b))
             for i, (a, b) in enumerate(rng.normal(size=(300, 2)) * 50)]
    xs = np.exp(rng.uniform(0, 10, size=64))
    seq = HullQueue()
    for k, a, b in lines:
        seq.insert(k, a, b)
    bulk = HullQueue()
    bulk.bulk_load(lines)
    assert len(seq) == len(bulk) == 300
    for got, want in zip(_envelope(bulk, xs), _envelope(seq, xs)):
        assert got is not None and want is not None
        assert math.isclose(got[1], want[1], rel_tol=1e-12)


def test_insert_many_then_ops_matches_reference():
    rng = np.random.default_rng(6)
    q = HullQueue()
    ref: dict = {}
    key = 0
    for _ in range(30):  # interleave bulk loads with deletes/updates/queries
        chunk = [(key + j, float(a), float(b))
                 for j, (a, b) in enumerate(rng.normal(size=(17, 2)) * 40)]
        key += len(chunk)
        q.insert_many(chunk)
        ref.update({k: (a, b) for k, a, b in chunk})
        for k in list(ref)[:: 5]:
            if rng.random() < 0.5:
                q.delete(k)
                del ref[k]
            else:
                a, b = rng.normal(size=2) * 40
                q.update(k, float(a), float(b))
                ref[k] = (float(a), float(b))
        x = float(np.exp(rng.uniform(0, 8)))
        got = q.argmax(x)
        want = max(ref.values(), key=lambda ab: ab[0] * x + ab[1])
        assert got is not None
        assert math.isclose(got[1], want[0] * x + want[1],
                            rel_tol=1e-9, abs_tol=1e-9)
    assert len(q) == len(ref)


def test_insert_many_validates_before_mutating():
    q = HullQueue()
    q.insert("a", 1.0, 2.0)
    with pytest.raises(KeyError):
        q.insert_many([("b", 1.0, 1.0), ("a", 2.0, 2.0)])  # dup vs existing
    assert "b" not in q and len(q) == 1  # nothing was half-inserted
    with pytest.raises(KeyError):
        q.insert_many([("c", 1.0, 1.0), ("c", 2.0, 2.0)])  # dup within batch
    assert "c" not in q
    with pytest.raises(ValueError):
        q.insert_many([("d", math.inf, 0.0)])
    assert "d" not in q


# ------------------------------------------------------------- on_arrivals
def _dists():
    return {
        "a": EmpiricalDistribution(np.array([10.0, 30.0]), np.array([1.0])),
        "b": EmpiricalDistribution(np.array([80.0, 120.0]), np.array([1.0])),
    }


def test_on_arrivals_equals_sequential_on_arrival():
    """Bulk delivery leaves the scheduler in the same state as the
    request-at-a-time path: same pending set, same hull envelopes, same
    batch decisions."""
    def mk_reqs():
        return [
            Request(app_id="a" if i % 3 else "b", release=0.0,
                    slo=300.0 + 40.0 * i, true_time=20.0, rid=1_000 + i,
                    cost=1.0 + (i % 2),
                    extra_deadlines=((700.0 + 40.0 * i, 3.0),) if i % 4 == 0
                    else ())
            for i in range(24)
        ]

    bulk = OrlojScheduler(LM, initial_dists=_dists())
    seq = OrlojScheduler(LM, initial_dists=_dists())
    bulk.on_arrivals(mk_reqs(), now=0.0)
    for r in mk_reqs():
        seq.on_arrival(r, now=0.0)

    assert bulk.n_pending == seq.n_pending
    assert set(bulk._pending) == set(seq._pending)
    xs = np.exp(np.linspace(0.0, 0.05, 7))
    for bs in bulk.cfg.batch_sizes:
        hb, hs = bulk._bs_state[bs].hull, seq._bs_state[bs].hull
        assert set(hb.keys()) == set(hs.keys())
        for k in hb.keys():
            for x in xs:
                assert hb.value(k, float(x)) == hs.value(k, float(x))
    assert sorted(bulk._milestones) == sorted(seq._milestones)

    ba, _ = bulk.next_batch(10.0)
    sa, _ = seq.next_batch(10.0)
    assert ba is not None and sa is not None
    assert ba.batch_size == sa.batch_size
    assert {r.rid for r in ba.requests} == {r.rid for r in sa.requests}


def test_on_arrivals_empty_is_noop():
    s = OrlojScheduler(LM, initial_dists=_dists())
    s.on_arrivals([], now=0.0)
    assert s.n_pending == 0
    batch, wake = s.next_batch(0.0)
    assert batch is None


def test_same_timestamp_burst_multiworker_all_policies():
    """Coalesced bursts: same-release arrivals are routed with each idle
    dispatch visible to later picks (a burst over an idle pool spreads
    across workers instead of piling onto one), and everything is
    conserved under every policy."""
    from repro.core import ModelExecutor, Worker, run_event_loop
    from repro.core.eventloop import DISPATCH_POLICIES

    for policy in DISPATCH_POLICIES:
        reqs = [
            Request(app_id="a", release=float(200 * (i // 8)),
                    slo=4_000.0, true_time=20.0)
            for i in range(48)  # bursts of 8 at t = 0, 200, 400, ...
        ]
        dispatch_log: list[tuple[int, float, int]] = []

        def mk_exec(i: int):
            inner = ModelExecutor(LM)

            def run(batch, now):
                dispatch_log.append((i, now, len(batch.requests)))
                return inner(batch, now)

            return run

        workers = [
            Worker(OrlojScheduler(LM, initial_dists=_dists()), mk_exec(i))
            for i in range(3)
        ]
        res = run_event_loop(reqs, workers, policy=policy, seed=3)
        assert res.n_total == 48, policy
        assert (res.n_finished_ok + res.n_finished_late + res.n_dropped
                + res.n_unserved) == 48, policy
        assert res.n_unserved == 0, policy
        # the burst head grabs an idle worker at its release instant …
        assert any(now == 0.0 for _, now, _ in dispatch_log), policy
        # … and load-aware routing sees that dispatch: the 8-deep burst
        # over 3 idle workers starts on at least two of them at t = 0
        if policy in ("least_loaded", "jsq_work", "round_robin"):
            assert len({i for i, now, _ in dispatch_log if now == 0.0}) >= 2, (
                policy
            )


def test_recompute_after_base_reset_uses_bulk_path():
    """Base reset far in the future recomputes every score; values must
    stay base-shift invariant and the scheduler keeps serving."""
    s = OrlojScheduler(LM, initial_dists=_dists())
    reqs = [Request(app_id="a", release=0.0, slo=10_000_000.0, true_time=20.0)
            for _ in range(32)]
    s.on_arrivals(reqs, now=0.0)
    # drive past the reset threshold: b·(t − base) > RESET_EXPONENT
    t = 700_000.0
    batch, _ = s.next_batch(t)
    assert s._base == t  # reset happened
    assert batch is not None and len(batch) >= 1
