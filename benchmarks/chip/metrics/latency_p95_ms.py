"""latency_p95_ms: 95th percentile of completion minus scheduled arrival,
over the served requests released in the window (atomic mixes)."""

import stats


def read(run):
    return stats.p95(stats.latencies(run.window.requests)) if run.kind == "atomic" else None
