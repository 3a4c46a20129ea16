"""Property tests for the dynamic convex-hull priority queue (paper §4.4)."""

import math

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.hull import HullQueue


def brute_argmax(entries: dict, x: float):
    if not entries:
        return None
    k = max(entries, key=lambda kk: entries[kk][0] * x + entries[kk][1])
    return k, entries[k][0] * x + entries[k][1]


def test_basic_insert_query_delete():
    q = HullQueue()
    q.insert("a", 1.0, 0.0)
    q.insert("b", -1.0, 10.0)
    # at small x, b wins (intercept); at large x, a wins (slope)
    assert q.argmax(0.1)[0] == "b"
    assert q.argmax(100.0)[0] == "a"
    q.delete("a")
    assert q.argmax(100.0)[0] == "b"
    q.delete("b")
    assert q.argmax(1.0) is None


def test_update_changes_line():
    q = HullQueue()
    q.insert(1, 1.0, 0.0)
    q.insert(2, 0.5, 0.0)
    assert q.argmax(1.0)[0] == 1
    q.update(1, 0.1, 0.0)
    assert q.argmax(1.0)[0] == 2


def test_pop_max_sequence():
    q = HullQueue()
    for i in range(10):
        q.insert(i, float(i), 0.0)
    got = [q.pop_max(1.0)[0] for _ in range(10)]
    assert got == list(range(9, -1, -1))
    assert q.pop_max(1.0) is None


def test_duplicate_insert_raises():
    q = HullQueue()
    q.insert("k", 1.0, 2.0)
    with pytest.raises(KeyError):
        q.insert("k", 3.0, 4.0)


def test_equal_slopes_keep_best_intercept():
    q = HullQueue()
    q.insert("lo", 2.0, 1.0)
    q.insert("hi", 2.0, 5.0)
    key, val = q.argmax(3.0)
    assert key == "hi" and val == pytest.approx(11.0)


@given(
    ops=st.lists(
        st.tuples(
            st.sampled_from(["ins", "del", "query", "update"]),
            st.integers(0, 30),
            st.floats(-100, 100, allow_nan=False),
            st.floats(-100, 100, allow_nan=False),
        ),
        min_size=1,
        max_size=200,
    ),
    xs=st.lists(st.floats(0.01, 1e6), min_size=1, max_size=5),
)
@settings(max_examples=120, deadline=None)
def test_property_matches_bruteforce(ops, xs):
    q = HullQueue()
    ref: dict = {}
    for op, key, a, b in ops:
        if op == "ins" and key not in ref:
            q.insert(key, a, b)
            ref[key] = (a, b)
        elif op == "del" and key in ref:
            q.delete(key)
            del ref[key]
        elif op == "update" and key in ref:
            q.update(key, a, b)
            ref[key] = (a, b)
        elif op == "query":
            for x in xs:
                got = q.argmax(x)
                want = brute_argmax(ref, x)
                if want is None:
                    assert got is None
                else:
                    assert got is not None
                    # value must match the true max (keys may tie)
                    assert math.isclose(got[1], want[1], rel_tol=1e-9, abs_tol=1e-9)
    assert len(q) == len(ref)
    for x in xs:
        got, want = q.argmax(x), brute_argmax(ref, x)
        if want is None:
            assert got is None
        else:
            assert math.isclose(got[1], want[1], rel_tol=1e-9, abs_tol=1e-9)


def test_many_interleaved_ops_random():
    rng = np.random.default_rng(0)
    q = HullQueue()
    ref: dict = {}
    next_key = 0
    for step in range(5_000):
        r = rng.random()
        if r < 0.5 or not ref:
            a, b = rng.normal(size=2) * 50
            q.insert(next_key, a, b)
            ref[next_key] = (a, b)
            next_key += 1
        elif r < 0.8:
            k = int(rng.choice(list(ref)))
            q.delete(k)
            del ref[k]
        else:
            x = float(np.exp(rng.uniform(0, 10)))
            got, want = q.argmax(x), brute_argmax(ref, x)
            assert got is not None
            assert math.isclose(got[1], want[1], rel_tol=1e-9, abs_tol=1e-7)
