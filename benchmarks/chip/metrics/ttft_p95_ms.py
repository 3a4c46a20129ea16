"""ttft_p95_ms: 95th percentile of first token minus scheduled arrival, over
the served token requests released in the window."""

import stats


def read(run):
    return stats.p95(stats.ttfts(run.window.requests)) if run.kind == "tokens" else None
