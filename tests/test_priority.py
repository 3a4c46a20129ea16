"""Tests for the Eq.-2 time-varying priority score (paper §4.1, §4.4, App. B)."""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.distributions import EmpiricalDistribution
from repro.core.priority import DEFAULT_B, BinScoreModel
from repro.core.request import Request


def _model(b=DEFAULT_B, edges=(20.0, 60.0, 120.0, 260.0), probs=(0.5, 0.3, 0.2)):
    d = EmpiricalDistribution(np.array(edges), np.array(probs))
    return BinScoreModel(d, b=b)


def _req(release=0.0, slo=500.0, cost=1.0, **kw):
    return Request(app_id="a", release=release, slo=slo, true_time=10.0, cost=cost, **kw)


def test_alpha_beta_matches_literal_eq2():
    m = _model()
    r = _req()
    for t in np.linspace(0.0, 600.0, 97):
        assert np.isclose(
            m.value(r, t, base=0.0), m.value_reference(r, t, base=0.0), rtol=1e-9
        ), t


def test_regimes_and_zero_after_hopeless():
    m = _model()
    r = _req(slo=500.0)
    # After D − l1_min (= 500 − 20) every bin is in regime C: score 0.
    assert m.value(r, 490.0, 0.0) == pytest.approx(0.0, abs=1e-12)
    # Well before the deadline the score is positive and *increasing*.
    v1, v2 = m.value(r, 0.0, 0.0), m.value(r, 100.0, 0.0)
    assert 0 < v1 < v2


def test_continuity_at_milestones():
    """p(t) is continuous across the D−l2 / D−l1 regime changes."""
    m = _model()
    r = _req(slo=400.0)
    for edge in np.concatenate([m.l1, m.l2]):
        t = r.deadline - edge
        lo, hi = m.value(r, t - 1e-6, 0.0), m.value(r, t + 1e-6, 0.0)
        assert np.isclose(lo, hi, rtol=1e-6, atol=1e-7)


def test_milestone_is_next_regime_change():
    m = _model()
    r = _req(slo=400.0)
    sc = m.score(r, 0.0, 0.0)
    # milestone = min over future D−l2, D−l1
    expected = min(
        min(r.deadline - m.l2), min(r.deadline - m.l1)
    )
    assert np.isclose(sc.milestone, expected)
    # just after the milestone the (α, β) must change
    sc2 = m.score(r, sc.milestone + 1e-9, 0.0)
    assert (sc.alpha, sc.beta) != (sc2.alpha, sc2.beta)


def test_base_shift_invariance():
    """Scores are invariant to the overflow-handling base shift (§4.4)."""
    m = _model()
    r = _req(release=1_000.0, slo=400.0)
    t = 1_100.0
    assert np.isclose(m.value(r, t, base=0.0), m.value(r, t, base=900.0), rtol=1e-9)


def test_earlier_deadline_scores_higher():
    m = _model()
    t = 0.0
    r1 = _req(release=0.0, slo=400.0)
    r2 = _req(release=0.0, slo=800.0)
    assert m.value(r1, t, 0.0) > m.value(r2, t, 0.0)


def test_cost_scales_score():
    m = _model()
    r1 = _req(cost=1.0)
    r5 = _req(cost=5.0)
    assert np.isclose(5 * m.value(r1, 10.0, 0.0), m.value(r5, 10.0, 0.0), rtol=1e-9)


def test_piecewise_step_cost_decomposition():
    """Appendix B: a multi-step cost is the sum of single-step scores."""
    m = _model()
    # deadlines at slo and slo+200 with cumulative costs 1 and 3.
    multi = _req(slo=400.0, cost=1.0, extra_deadlines=((600.0, 3.0),))
    s1 = _req(slo=400.0, cost=1.0)
    s2 = _req(slo=600.0, cost=2.0)
    for t in (0.0, 150.0, 350.0, 450.0, 590.0):
        assert np.isclose(
            m.value(multi, t, 0.0),
            m.value(s1, t, 0.0) + m.value(s2, t, 0.0),
            rtol=1e-9,
        ), t


def test_b_does_not_change_ordering():
    """§5.6: the relative ordering of requests is insensitive to b."""
    reqs = [_req(release=float(i * 30), slo=400.0 + 50 * i) for i in range(6)]
    orders = []
    for b in (1e-5, 1e-4, 1e-3):
        m = _model(b=b)
        vals = [m.value(r, 100.0, 0.0) for r in reqs]
        orders.append(tuple(np.argsort(vals)))
    assert orders[0] == orders[1] == orders[2]


@given(
    slo=st.floats(min_value=300.0, max_value=5_000.0),
    t=st.floats(min_value=0.0, max_value=5_000.0),
    base=st.floats(min_value=-1_000.0, max_value=1_000.0),
)
@settings(max_examples=60, deadline=None)
def test_property_score_nonnegative_finite(slo, t, base):
    m = _model()
    r = _req(slo=slo)
    v = m.value(r, t, base)
    assert np.isfinite(v)
    assert v >= -1e-9
