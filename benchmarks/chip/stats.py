"""Arithmetic of the end-to-end metrics, on the requests of one window.

Latencies are client-side: from a request's scheduled arrival (``release``)
to its completion on the wall clock.  Every request released in the window
counts in ``finish_rate``; dropped, failed, late and unfinished ones count
as misses.  A request the scheduler sheds (drops or rejects because it can
no longer meet its SLO) is answered with that refusal: it is a miss, not a
failure.  A failure is a request the program lost: one that failed, or that
was neither served nor shed by the end of the drain.
"""

from __future__ import annotations

import numpy as np


def p95(values) -> float | None:
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, 95)) if v.size else None


def finish_rate(reqs, ttft_ms: float | None = None) -> float:
    """Percent of ``reqs`` that finished by their deadline (release + slo)
    and, where ``ttft_ms`` is given, had their first token within it."""
    ok = 0
    for r in reqs:
        if r.finished is None or r.finished > r.release + r.slo:
            continue
        if ttft_ms is not None and (r.first_token is None or r.first_token - r.release > ttft_ms):
            continue
        ok += 1
    return 100.0 * ok / len(reqs)


def shed(reqs) -> int:
    """Requests the scheduler dropped or rejected."""
    return sum(1 for r in reqs if r.dropped is not None or r.rejected is not None)


def failed(reqs) -> int:
    """Requests that failed, or that were neither served nor shed."""
    return sum(
        1 for r in reqs
        if r.failed is not None or (r.finished is None and r.dropped is None and r.rejected is None)
    )


def latencies(reqs) -> list[float]:
    return [r.finished - r.release for r in reqs if r.finished is not None]


def ttfts(reqs) -> list[float]:
    return [r.first_token - r.release for r in reqs if r.finished is not None]


def tpots(reqs) -> list[float]:
    """Per served request with more than one output token: the mean gap
    between its output tokens."""
    return [
        (r.finished - r.first_token) / (r.out_tokens - 1)
        for r in reqs
        if r.finished is not None and r.out_tokens > 1
    ]
