"""tpot_p95_ms: 95th percentile over served token requests with more than
one output token of (finish - first token) / (output tokens - 1)."""

import stats


def read(run):
    return stats.p95(stats.tpots(run.window.requests)) if run.kind == "tokens" else None
