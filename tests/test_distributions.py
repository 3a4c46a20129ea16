"""Tests for empirical distributions and order statistics (paper §4.2)."""

import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.distributions import (
    BatchLatencyModel,
    EmpiricalDistribution,
    hetero_max,
    iid_max,
    mixture,
    ozbey_max_pdf,
    _pdf,
)


def _dist(rng, n_bins=8, lo=1.0, hi=100.0):
    edges = np.sort(rng.uniform(lo, hi, size=n_bins + 1))
    edges += np.arange(n_bins + 1) * 1e-3  # ensure strictly increasing
    probs = rng.random(n_bins) + 1e-3
    return EmpiricalDistribution(edges, probs)


# ---------------------------------------------------------------- basics
def test_normalization_and_mean():
    d = EmpiricalDistribution(np.array([0.0, 1.0, 2.0]), np.array([2.0, 2.0]))
    assert np.isclose(d.probs.sum(), 1.0)
    assert np.isclose(d.mean(), 1.0)


def test_cdf_monotone_and_bounds():
    rng = np.random.default_rng(0)
    d = _dist(rng)
    xs = np.linspace(d.lo - 5, d.hi + 5, 300)
    cdf = d.cdf(xs)
    assert np.all(np.diff(cdf) >= -1e-12)
    assert cdf[0] == 0.0 and cdf[-1] == 1.0


def test_from_samples_and_quantile():
    rng = np.random.default_rng(1)
    samples = rng.lognormal(3.0, 0.5, size=20_000)
    d = EmpiricalDistribution.from_samples(samples, n_bins=64)
    assert np.isclose(d.mean(), samples.mean(), rtol=0.05)
    assert np.isclose(d.quantile(0.5), np.median(samples), rtol=0.1)


def test_delta_distribution():
    d = EmpiricalDistribution.delta(42.0)
    assert np.isclose(d.mean(), 42.0, rtol=1e-2)
    assert d.expected_max(100) <= d.hi


# ------------------------------------------------------- order statistics
def test_iid_max_cdf_is_power():
    """Eq. 6: F_(k) = F^k at the knots."""
    rng = np.random.default_rng(2)
    d = _dist(rng)
    k = 5
    dk = iid_max(d, k)
    assert np.allclose(dk.cdf_at_knots(), d.cdf_at_knots() ** k, atol=1e-12)


def test_expected_max_monte_carlo():
    rng = np.random.default_rng(3)
    d = _dist(rng)
    for k in (1, 2, 4, 16):
        samp = d.sample(rng, size=200_000 // max(k // 4, 1) * k).reshape(-1, k)
        mc = samp.max(axis=1).mean()
        assert np.isclose(d.expected_max(k), mc, rtol=0.02), k


@given(k=st.integers(min_value=1, max_value=64), seed=st.integers(0, 1_000))
@settings(max_examples=30, deadline=None)
def test_expected_max_monotone_in_k(k, seed):
    rng = np.random.default_rng(seed)
    d = _dist(rng)
    e1 = d.expected_max(k)
    e2 = d.expected_max(k + 1)
    assert e2 >= e1 - 1e-9
    assert d.lo - 1e-9 <= e1 <= d.hi + 1e-9


def test_hetero_max_identical_matches_iid():
    rng = np.random.default_rng(4)
    d = _dist(rng)
    hk = hetero_max([d, d, d])
    ik = iid_max(d, 3)
    xs = np.linspace(d.lo, d.hi, 200)
    assert np.allclose(hk.cdf(xs), ik.cdf(xs), atol=5e-3)


def test_hetero_max_mc():
    rng = np.random.default_rng(5)
    ds = [_dist(rng, lo=1, hi=50), _dist(rng, lo=20, hi=120), _dist(rng, lo=5, hi=80)]
    hm = hetero_max(ds)
    samp = np.stack([d.sample(rng, 100_000) for d in ds]).max(axis=0)
    assert np.isclose(hm.mean(), samp.mean(), rtol=0.02)


def test_ozbey_reduces_to_product_cdf():
    """Literal Eq. 8 (k-th order statistic PDF) integrates to the same CDF
    as the product form ``Π F_i`` our implementation uses."""
    rng = np.random.default_rng(6)
    ds = [_dist(rng, n_bins=4, lo=1, hi=40), _dist(rng, n_bins=4, lo=10, hi=60)]
    xs = np.linspace(0.0, 70.0, 4_000)
    pdf = ozbey_max_pdf(ds, xs)
    cdf_from_eq8 = np.cumsum(pdf) * (xs[1] - xs[0])
    cdf_product = ds[0].cdf(xs) * ds[1].cdf(xs)
    assert np.allclose(cdf_from_eq8, cdf_product, atol=2e-2)


def test_ozbey_three_way():
    rng = np.random.default_rng(7)
    ds = [_dist(rng, n_bins=3, lo=1, hi=30) for _ in range(3)]
    xs = np.linspace(0.0, 35.0, 2_000)
    pdf = ozbey_max_pdf(ds, xs)
    cdf_from_eq8 = np.cumsum(pdf) * (xs[1] - xs[0])
    prod = np.ones_like(xs)
    for d in ds:
        prod *= d.cdf(xs)
    assert np.allclose(cdf_from_eq8, prod, atol=3e-2)


# ------------------------------------------------------------- mixtures
def test_mixture_mean():
    rng = np.random.default_rng(8)
    d1, d2 = _dist(rng, lo=1, hi=20), _dist(rng, lo=50, hi=90)
    m = mixture([d1, d2], weights=[0.25, 0.75])
    assert np.isclose(m.mean(), 0.25 * d1.mean() + 0.75 * d2.mean(), rtol=1e-2)


# ------------------------------------------------------- batch latency
def test_batch_latency_model_eq3():
    lm = BatchLatencyModel(c0=5.0, c1=2.0)
    assert lm.batch_time([3.0, 7.0, 1.0]) == 5.0 + 2.0 * 3 * 7.0


def test_batch_dist_affine():
    rng = np.random.default_rng(9)
    d = _dist(rng)
    lm = BatchLatencyModel(c0=5.0, c1=2.0)
    k = 4
    bd = lm.batch_dist(iid_max(d, k), k)
    assert np.isclose(bd.mean(), 5.0 + 2.0 * k * iid_max(d, k).mean(), rtol=1e-9)
    assert np.isclose(lm.expected_batch_time(d, k), 5.0 + 2.0 * k * d.expected_max(k))


def test_bucketed_batch_dist():
    """TPU padded-bucket variant: mass collapses onto bucket boundaries."""
    d = EmpiricalDistribution(np.array([10.0, 90.0]), np.array([1.0]))
    lm = BatchLatencyModel(c0=0.0, c1=1.0, bucket=32.0)
    bd = lm.batch_dist(d, 1)
    # Support must lie (just below) multiples of 32.
    mids = 0.5 * (bd.edges[:-1] + bd.edges[1:])
    mass_bins = mids[bd.probs > 1e-12]
    assert np.all((np.ceil(mass_bins / 32.0) * 32.0 - mass_bins) < 1.0)
    assert lm.batch_time([33.0]) == 64.0


def test_pdf_consistent_with_cdf():
    rng = np.random.default_rng(10)
    d = _dist(rng)
    xs = np.linspace(d.lo, d.hi, 5_000)
    approx_cdf = np.cumsum(_pdf(d, xs)) * (xs[1] - xs[0])
    assert np.allclose(approx_cdf, d.cdf(xs), atol=2e-2)


# -------------------------------------------------------------- fuzzing
@given(
    seed=st.integers(0, 10_000),
    n_bins=st.integers(1, 24),
    k=st.integers(1, 12),
)
@settings(max_examples=40, deadline=None)
def test_property_iid_max_valid_distribution(seed, n_bins, k):
    rng = np.random.default_rng(seed)
    d = _dist(rng, n_bins=n_bins)
    dk = iid_max(d, k)
    assert np.isclose(dk.probs.sum(), 1.0)
    assert np.all(dk.probs >= -1e-12)
    # max stochastically dominates the base distribution
    xs = np.linspace(d.lo, d.hi, 50)
    assert np.all(dk.cdf(xs) <= d.cdf(xs) + 1e-9)
