"""Property tests for the conditional-tail machinery of
``EmpiricalDistribution`` (``repro.core.distributions``): the per-step
remaining-length view token-level scheduling leans on (DESIGN.md §12).

Three contracts, property-tested across random mixtures when hypothesis
is installed (example-based pins always run):

- ``E[X | X > t] = t + expected_remaining(t)`` is nondecreasing in the
  conditioning point ``t`` — true for *any* distribution, even though
  ``expected_remaining`` itself is not monotone for multimodal mixtures;
- ``conditional_tail(t)`` is consistent with direct truncation: its CDF
  is ``(F(x) − F(t)) / (1 − F(t))`` and its mean is
  ``t + expected_remaining(t)`` (both exact under the piecewise-linear
  CDF, so the comparison is tight, not approximate);
- EOS-histogram edge cases: mass in the first bin at 0, a single-knot
  delta, and conditioning at/beyond the end of support stay loud or
  exact rather than silently degenerate.
"""

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.distributions import EmpiricalDistribution

RTOL = 1e-9


def _mixture(samples, n_bins=8):
    return EmpiricalDistribution.from_samples(samples, n_bins=n_bins)


def _bimodal():
    # two well-separated peaks: expected_remaining is non-monotone here
    # (it jumps up after the first peak drains), the conditioned mean is not
    return EmpiricalDistribution(
        np.array([1.0, 2.0, 40.0, 50.0]), np.array([0.7, 0.0, 0.3])
    )


# ------------------------------------------------------- example-based pins
def test_conditional_mean_monotone_even_when_remaining_is_not():
    d = _bimodal()
    ts = np.linspace(d.lo, d.hi, 200, endpoint=False)[1:]
    er = np.array([d.expected_remaining(float(t)) for t in ts])
    cond_mean = ts + er
    assert np.all(np.diff(cond_mean) >= -RTOL)
    # sanity: the raw remaining time itself genuinely dips and recovers,
    # so the monotonicity above is not vacuous
    assert np.min(np.diff(er)) < -1e-6 < 1e-6 < np.max(np.diff(er))


def test_conditional_tail_matches_direct_truncation():
    d = _mixture(np.concatenate([
        np.linspace(1.0, 5.0, 40), np.linspace(20.0, 30.0, 20)
    ]))
    for t in (1.5, 4.0, 12.0, 25.0):
        tail = d.conditional_tail(t)
        # support starts exactly at the conditioning point
        assert tail.lo == pytest.approx(t)
        assert tail.hi == pytest.approx(d.hi)
        # CDF identity: F_tail(x) = (F(x) - F(t)) / (1 - F(t))
        xs = np.linspace(t, d.hi, 50)
        ft = float(d.cdf(t))
        np.testing.assert_allclose(
            tail.cdf(xs), (d.cdf(xs) - ft) / (1.0 - ft), atol=1e-12
        )
        # mean identity: E[X | X > t] - t = expected_remaining(t), exact
        assert tail.mean() - t == pytest.approx(
            d.expected_remaining(t), rel=RTOL
        )


def test_mass_at_zero_eos_histogram():
    # an EOS histogram whose first bin starts at 0 with most of the mass:
    # the "already likely done" shape continuous batching produces
    d = EmpiricalDistribution(
        np.array([0.0, 0.5, 4.0]), np.array([0.8, 0.2])
    )
    assert d.lo == 0.0
    assert d.expected_remaining(0.0) > 0.0
    # conditioning inside the zero bin renormalizes, not crashes
    tail = d.conditional_tail(0.25)
    assert tail.lo == pytest.approx(0.25)
    assert tail.mean() - 0.25 == pytest.approx(
        d.expected_remaining(0.25), rel=RTOL
    )
    # conditioning at or below the support start returns the identity
    assert d.conditional_tail(0.0) is d
    assert d.conditional_tail(-1.0) is d


def test_single_knot_delta():
    d = EmpiricalDistribution.delta(5.0)
    assert d.conditional_tail(0.0) is d
    t = d.lo + 0.25 * (d.hi - d.lo)
    tail = d.conditional_tail(t)
    assert tail.lo == pytest.approx(t)
    assert tail.mean() - t == pytest.approx(d.expected_remaining(t), rel=RTOL)
    # a delta's remaining time collapses to ~0 at the scale of its width
    assert d.expected_remaining(t) <= (d.hi - d.lo)


def test_beyond_support_is_loud_or_zero():
    d = _mixture(np.linspace(1.0, 10.0, 30))
    # expected_remaining degrades gracefully: "expected to finish now"
    assert d.expected_remaining(d.hi) == 0.0
    assert d.expected_remaining(d.hi + 5.0) == 0.0
    # conditional_tail cannot represent an empty distribution: loud
    with pytest.raises(ValueError, match="no mass above"):
        d.conditional_tail(d.hi)
    with pytest.raises(ValueError, match="no mass above"):
        d.conditional_tail(d.hi + 5.0)


# ----------------------------------------------------------- property tests
def _dist_and_t(samples, n_bins, frac):
    d = _mixture(samples, n_bins=n_bins)
    t = d.lo + frac * (d.hi - d.lo)
    return d, float(t)


@settings(max_examples=60, deadline=None)
@given(
    samples=st.lists(
        st.floats(min_value=0.0, max_value=500.0,
                  allow_nan=False, allow_infinity=False),
        min_size=2, max_size=60,
    ),
    n_bins=st.integers(min_value=1, max_value=24),
    fa=st.floats(min_value=0.001, max_value=0.999),
    fb=st.floats(min_value=0.001, max_value=0.999),
)
def test_property_conditional_mean_monotone(samples, n_bins, fa, fb):
    d = _mixture(samples, n_bins=n_bins)
    ta, tb = sorted(
        d.lo + f * (d.hi - d.lo) for f in (fa, fb)
    )
    ga = ta + d.expected_remaining(ta)
    gb = tb + d.expected_remaining(tb)
    assert gb >= ga - RTOL * max(1.0, abs(ga))


@settings(max_examples=60, deadline=None)
@given(
    samples=st.lists(
        st.floats(min_value=0.0, max_value=500.0,
                  allow_nan=False, allow_infinity=False),
        min_size=2, max_size=60,
    ),
    n_bins=st.integers(min_value=1, max_value=24),
    frac=st.floats(min_value=0.001, max_value=0.98),
)
def test_property_tail_consistent_with_truncation(samples, n_bins, frac):
    d, t = _dist_and_t(samples, n_bins, frac)
    try:
        tail = d.conditional_tail(t)
    except ValueError:
        # all mass at/below t (histograms can leave empty upper bins):
        # the mean view must agree that nothing remains
        assert d.expected_remaining(t) == 0.0
        return
    if t <= d.lo:
        assert tail is d
        return
    assert tail.lo == pytest.approx(t)
    assert tail.mean() - t == pytest.approx(
        d.expected_remaining(t), rel=1e-7, abs=1e-9
    )
    ft = float(d.cdf(t))
    xs = np.linspace(t, d.hi, 20)
    np.testing.assert_allclose(
        tail.cdf(xs), (d.cdf(xs) - ft) / (1.0 - ft), atol=1e-9
    )
