"""Property tests for the array engine's event sourcing: the calendar-queue
:class:`EventWheel` (total order ≡ heapq, bucket-boundary and overflow
edges) and the columnar :class:`RequestStore` (sorting, groups, row
mapping, stats folding) — DESIGN.md §10."""

import heapq
import math

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.eventwheel import MAX_BUCKET_SPAN, EventWheel
from repro.core.request import Request
from repro.core.requeststore import RequestStore


def _random_events(rng, n, *, tick=None, t_max=1_000.0):
    times = rng.uniform(0.0, t_max, size=n)
    if tick:
        times = np.floor(times / tick) * tick  # force heavy timestamp ties
    return [(float(t), i, 0, None) for i, t in enumerate(times)]


def _heapq_order(events):
    h = list(events)
    heapq.heapify(h)
    return [heapq.heappop(h) for _ in range(len(h))]


# ------------------------------------------------------------ EventWheel
@pytest.mark.parametrize("bucket_ms", [None, 0.5, 4.0, 1_000.0, 1e9])
@pytest.mark.parametrize("tick", [None, 4.0])
def test_drain_matches_heapq(bucket_ms, tick):
    """Total order across buckets/overflow ≡ a heapq over (time, seq),
    for bucket widths from far-finer to far-coarser than the spread and
    for continuous as well as heavily tied (tick-quantized) timestamps."""
    rng = np.random.default_rng(0)
    events = _random_events(rng, 500, tick=tick)
    w = EventWheel(bucket_ms)
    for ev in events:
        w.push(*ev)
    assert len(w) == len(events)
    assert list(w.drain()) == _heapq_order(events)
    assert len(w) == 0 and not w


def test_same_timestamp_coalesce_one_batch():
    """Equal-time events land in one bucket and drain as one seq-sorted
    batch — the coalescing window the bulk arrival path feeds on."""
    w = EventWheel(4.0)
    for seq in (5, 1, 3):
        w.push(7.5, seq, 0, f"p{seq}")
    batch = w.pop_bucket()
    assert [(t, s) for t, s, _, _ in batch] == [(7.5, 1), (7.5, 3), (7.5, 5)]


def test_bucket_boundary_edges():
    """t exactly on a bucket edge belongs to the *upper* bucket
    (floor(t / width)); just-below stays in the lower one."""
    w = EventWheel(4.0)
    eps = 1e-9
    w.push(8.0, 1, 0, None)        # bucket 2
    w.push(8.0 - eps, 0, 0, None)  # bucket 1
    first = w.pop_bucket()
    assert [s for _, s, _, _ in first] == [0]
    assert [s for _, s, _, _ in w.pop_bucket()] == [1]


def test_overflow_nonfinite_and_far_future():
    """Non-finite and pathologically far timestamps take the heapq
    fallback but still merge back in global (time, seq) order."""
    w = EventWheel(1.0)
    far = (MAX_BUCKET_SPAN + 10) * 1.0  # beyond the bucket-span window
    w.push(math.inf, 3, 0, "inf")
    w.push(far, 2, 0, "far")
    w.push(5.0, 1, 0, "near")
    assert w.peek_key() == (5.0, 1)
    got = [(t, s) for t, s, _, _ in w.drain()]
    assert got == [(5.0, 1), (far, 2), (math.inf, 3)]


def test_overflow_merges_into_bucket_window():
    """An event pushed while outside the bucket-span window (→ overflow
    heap) still surfaces inside the right bucket's batch, sorted into
    place, once the cursor catches up and that bucket goes live."""
    bm = 2.0
    w = EventWheel(bm)
    near = MAX_BUCKET_SPAN * bm        # bucket idx = span: inside window
    far = 2 * MAX_BUCKET_SPAN * bm     # idx = 2*span: outside -> overflow
    w.push(near, 0, 0, None)
    w.push(far, 1, 0, None)
    assert [s for _, s, _, _ in w.pop_bucket()] == [0]  # cursor -> span
    w.push(far + 0.5, 2, 0, None)      # same bucket, now inside the window
    batch = w.pop_bucket()
    assert [(t, s) for t, s, _, _ in batch] == [(far, 1), (far + 0.5, 2)]


def _fault_tail_events(rng, n_near, n_far, bucket_ms):
    """Mixed near/far/non-finite stream shaped like a faulted run: normal
    DONE/WAKE traffic plus CRASH(kind 3)/RESTART(kind 4) events whose
    timestamps land far outside the bucket window (huge restart delays)
    or at +inf (a next-crash renewal past everything)."""
    _CRASH, _RESTART = 3, 4
    events = []
    seq = 0
    for t in rng.uniform(0.0, 500.0, size=n_near):
        events.append((float(t), seq, int(rng.integers(0, 3)), None))
        seq += 1
    far_base = (MAX_BUCKET_SPAN + 1) * bucket_ms
    for t in rng.uniform(far_base, far_base * 50, size=n_far):
        kind = _CRASH if seq % 2 else _RESTART
        events.append((float(t), seq, kind, seq % 4))
        seq += 1
    events.append((math.inf, seq, _CRASH, 0))
    return events


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("bucket_ms", [0.5, 4.0, 64.0])
def test_overflow_fault_events_keep_heapq_order(seed, bucket_ms):
    """Crash/restart events at far-future and non-finite timestamps (the
    shapes huge ``restart_delay_ms``/``mttf_ms`` plans produce) ride the
    overflow heap yet drain in exact (time, seq) heapq order, mixed
    pop/pop_bucket included."""
    rng = np.random.default_rng(seed)
    events = _fault_tail_events(rng, n_near=300, n_far=40, bucket_ms=bucket_ms)
    w = EventWheel(bucket_ms)
    for ev in events:
        w.push(*ev)
    got = []
    while w:
        if rng.random() < 0.5:
            got.append(w.pop())
        else:
            got.extend(w.pop_bucket())
    assert got == _heapq_order(events)


@settings(max_examples=50, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    bucket_ms=st.floats(min_value=1e-3, max_value=1e6),
    n_near=st.integers(min_value=0, max_value=200),
    n_far=st.integers(min_value=0, max_value=50),
)
def test_overflow_fault_order_property(seed, bucket_ms, n_near, n_far):
    """Property form of the above: arbitrary bucket widths and near/far
    mixes, total drain order ≡ heapq."""
    rng = np.random.default_rng(seed)
    events = _fault_tail_events(rng, n_near, n_far, bucket_ms)
    w = EventWheel(bucket_ms)
    for ev in events:
        w.push(*ev)
    assert list(w.drain()) == _heapq_order(events)


def test_push_before_last_pop_raises():
    w = EventWheel(4.0)
    w.push(10.0, 0, 0, None)
    w.pop_bucket()
    with pytest.raises(ValueError, match="pushed before"):
        w.push(9.0, 1, 0, None)
    # at the last-pop time is fine (same-instant follow-up events)
    w.push(10.0, 2, 0, None)


def test_push_during_drain_keeps_global_order():
    """Handlers may push fresh events between the remaining entries of a
    popped batch (DONE arming a WAKE); peek_key exposes them so the
    caller's merge preserves (time, seq) order."""
    w = EventWheel(10.0)
    w.push(1.0, 0, 0, None)
    w.push(9.0, 1, 0, None)
    batch = w.pop_bucket()
    assert [s for _, s, _, _ in batch] == [0, 1]
    w.push(5.0, 2, 0, None)  # between the two popped entries' times
    assert w.peek_key() == (5.0, 2)
    assert [s for _, s, _, _ in w.pop_bucket()] == [2]


def test_pop_single_matches_heapq_and_mixes_with_pop_bucket():
    rng = np.random.default_rng(3)
    events = _random_events(rng, 200, tick=2.0, t_max=100.0)
    w = EventWheel(4.0)
    for ev in events:
        w.push(*ev)
    got = []
    while w:
        if rng.random() < 0.5:
            got.append(w.pop())
        else:
            got.extend(w.pop_bucket())
    assert got == _heapq_order(events)


def test_empty_and_invalid():
    w = EventWheel(4.0)
    assert w.peek_key() == (math.inf, -1)
    assert w.peek_time() == math.inf
    with pytest.raises(IndexError):
        w.pop_bucket()
    with pytest.raises(IndexError):
        w.pop()
    with pytest.raises(ValueError, match="bucket_ms"):
        EventWheel(0.0)
    with pytest.raises(ValueError, match="bucket_ms"):
        EventWheel(-1.0)


# ---------------------------------------------------------- RequestStore
def _reqs(releases, slo=50.0):
    return [
        Request(app_id="a", release=float(t), slo=slo, true_time=1.0)
        for t in releases
    ]


def test_store_sorts_stably_and_groups():
    reqs = _reqs([5.0, 1.0, 5.0, 3.0, 1.0])
    store = RequestStore(reqs)
    assert [r.release for r in store.requests] == [1.0, 1.0, 3.0, 5.0, 5.0]
    # stable: equal-release requests keep input order
    assert store.requests == sorted(reqs, key=lambda r: r.release)
    assert store.group_times == [1.0, 3.0, 5.0]
    assert store.group_starts == [0, 2, 3, 5]
    assert store.group(0) == store.requests[0:2]
    assert store.n_groups == 3


def test_store_sorted_input_fast_path():
    reqs = _reqs([1.0, 2.0, 2.0, 7.0])
    store = RequestStore(reqs)
    assert store.requests == reqs  # no reorder
    assert store.release.tolist() == [1.0, 2.0, 2.0, 7.0]
    assert (store.deadline == store.release + 50.0).all()
    assert len(store) == 4
    assert len(RequestStore([])) == 0


def test_rows_for_contiguous_and_sparse_rids():
    reqs = _reqs([3.0, 1.0, 2.0])  # contiguous rids from the global counter
    store = RequestStore(reqs)
    assert store.rows_for([reqs[0], reqs[1]]) == [2, 0]
    assert isinstance(store._row, list)
    # sparse rids (hand-built subset) fall back to the dict map
    sparse = _reqs([4.0, 5.0, 6.0])[::2]
    store2 = RequestStore(sparse)
    assert store2.rows_for(list(reversed(sparse))) == [1, 0]
    assert isinstance(store2._row, dict)


def test_fold_stats_matches_scalar_accounting():
    reqs = _reqs([0.0, 1.0, 2.0, 3.0], slo=10.0)
    store = RequestStore(reqs)
    store.started[:] = [0.0, 1.0, np.nan, np.nan]
    store.finished[:] = [5.0, 20.0, np.nan, np.nan]  # ok, late, -, -
    store.requests[2].dropped = 2.5
    ok, late, dropped, unserved, lat = store.fold_stats()
    assert (ok, late, dropped, unserved) == (1, 1, 1, 1)
    assert lat.tolist() == [5.0, 19.0]
    # no_drops fast path: the proven-drop-free accounting
    store.requests[2].dropped = None
    ok, late, dropped, unserved, _ = store.fold_stats(no_drops=True)
    assert (ok, late, dropped, unserved) == (1, 1, 0, 2)


def test_writeback_flushes_only_written_rows():
    reqs = _reqs([0.0, 1.0])
    store = RequestStore(reqs)
    store.started[0] = 4.0
    store.finished[0] = 9.0
    store.writeback()
    assert (reqs[0].started, reqs[0].finished) == (4.0, 9.0)
    assert reqs[1].started is None and reqs[1].finished is None
