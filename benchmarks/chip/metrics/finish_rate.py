"""finish_rate (%): requests released in the window that completed within
their SLO, over all released; token requests also need their first token
within the TTFT limit."""

import stats


def read(run):
    ttft = run.mix["ttft_ms"] if run.kind == "tokens" else None
    return stats.finish_rate(run.window.requests, ttft)
