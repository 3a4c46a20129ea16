"""The one traffic generator: every mix is a data file in ``traffic/``.

A mix file fixes the served path (``kind``: ``atomic`` prefill batches or
``tokens`` continuous decode), the arrival process and its rate, the length
distributions and the latency limits, all as absolute numbers.  Nothing here
reads a measured latency, so a faster program neither raises its own load
nor tightens its own limits.

Every seed gets the same work: the multiset of lengths and of inter-arrival
gaps is drawn once from the mix's ``base_seed`` and only their order comes
from the run's ``--seed``.  Token ids, the run's permutations and the
scheduler's prior histograms come from streams of their own.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

KINDS = ("atomic", "tokens")


def load_mix(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind must be one of {KINDS}, got {mix.get('kind')!r}")
    return mix


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any non-negative seed."""
    return np.random.default_rng([int(seed), stream])


STREAM_ORDER, STREAM_TOKENS, STREAM_PRIOR, STREAM_CHECK, STREAM_WEIGHTS = 1, 2, 3, 4, 5


def sample_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths from a length spec, clipped to [lo, hi]."""
    dist = spec["dist"]
    if dist == "normal_mix":
        parts = spec["parts"]
        w = np.array([p["weight"] for p in parts], np.float64)
        pick = rng.choice(len(parts), size=n, p=w / w.sum())
        means = np.array([p["mean"] for p in parts])[pick]
        stds = np.array([p["std"] for p in parts])[pick]
        x = rng.normal(means, stds)
    elif dist == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    elif dist == "uniform_int":
        x = rng.integers(spec["lo"], spec["hi"] + 1, size=n).astype(np.float64)
    elif dist == "geometric":
        x = np.maximum(rng.geometric(1.0 / spec["mean"], size=n), 1).astype(np.float64)
    elif dist == "fixed":
        x = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo, hi = spec.get("lo", 1), spec.get("hi", np.inf)
    # int() of the clipped value, as the repo's bimodal_length does
    return np.clip(x, lo, hi).astype(np.int64)


def renewal_gaps(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n + 1`` unit-mean renewal gaps: exponential for ``poisson``, gamma
    with coefficient of variation ``cv`` for ``gamma``.  :func:`plan` scales
    them so that they span the window exactly."""
    proc = spec["process"]
    if proc == "poisson":
        gaps = rng.exponential(1.0, size=n + 1)
    elif proc == "gamma":
        shape = 1.0 / spec["cv"] ** 2
        gaps = rng.gamma(shape, 1.0 / shape, size=n + 1)
    else:
        raise ValueError(f"unknown arrival process {proc!r}")
    return gaps


def n_requests(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["arrivals"]["rate_per_s"] * seconds)))


def plan(mix: dict, seconds: float, seed: int) -> dict:
    """The window's work for one run: arrival offsets (ms), prompt lengths
    and, for token mixes, output lengths.  The multisets depend on the mix
    and ``seconds`` only; ``seed`` permutes them."""
    n = n_requests(mix, seconds)
    base = np.random.default_rng(int(mix["base_seed"]))
    lengths = sample_lengths(mix["prompt"], n, base)
    gaps = renewal_gaps(mix["arrivals"], n, base)
    outs = sample_lengths(mix["output"], n, base) if mix["kind"] == "tokens" else None
    order = rng_for(seed, STREAM_ORDER)
    gaps = order.permutation(gaps)
    arrivals = np.cumsum(gaps)[:-1] / gaps.sum() * (seconds * 1e3)
    perm = order.permutation(n)
    return {
        "arrivals_ms": arrivals,
        "lengths": lengths[perm],
        "outs": None if outs is None else outs[order.permutation(n)],
    }


def app_threshold(mix: dict) -> int:
    """Length that splits the mix into the scheduler's two apps ("short"
    at or below, "long" above): the median of the mix's own distribution,
    as the repo's make_requests splits at its sample median."""
    base = np.random.default_rng(int(mix["base_seed"]))
    return int(np.median(sample_lengths(mix["prompt"], 4096, base)))


def prior_lengths(mix: dict, seed: int, key: str = "prompt") -> np.ndarray:
    """Samples for the scheduler's prior histograms, from a stream that the
    window never draws from."""
    return sample_lengths(mix[key], int(mix["prior_samples"]), rng_for(seed, STREAM_PRIOR))


def token_ids(lengths: np.ndarray, vocab: int, seed: int) -> list[np.ndarray]:
    rng = rng_for(seed, STREAM_TOKENS)
    return [rng.integers(1, vocab, size=int(n)).astype(np.int32) for n in lengths]
