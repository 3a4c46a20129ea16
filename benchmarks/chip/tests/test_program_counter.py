"""CPU tests of the reader of the program's own counter ``SimResult.n_scored``.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

from __future__ import annotations

import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parents[1] / "src"))
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

NAME = "sched_scored_per_decision.prefill"


def run_with(result, kind: str = "atomic", n_decisions: int = 10) -> harness.Run:
    window = types.SimpleNamespace(requests=[], result=result, n_decisions=n_decisions,
                                   pacer=types.SimpleNamespace(sched_s=0.0), execs=[])
    return harness.Run({"name": "gpt-bimodal"}, {}, {"kind": kind}, 1.0, window, {}, None)


def read(run) -> float | None:
    return harness._load_module(harness.reader_path(NAME)).read(run)


def test_lines_scored_per_decision():
    assert read(run_with(types.SimpleNamespace(n_scored=120))) == 12.0


@pytest.mark.parametrize("result,kind,n_decisions", [
    (types.SimpleNamespace(), "atomic", 10),  # a SimResult without the counter
    (types.SimpleNamespace(n_scored=0), "atomic", 10),  # a scheduler that does not count
    (types.SimpleNamespace(n_scored=120), "atomic", 0),  # no decision in the window
    (types.SimpleNamespace(n_scored=120), "tokens", 10),  # the decode path
])
def test_nothing_where_the_program_does_not_count(result, kind, n_decisions):
    assert read(run_with(result, kind, n_decisions)) is None


def test_the_program_counts_what_the_reader_reads():
    from repro.core import BatchLatencyModel, ModelExecutor, OrlojScheduler, simulate
    from repro.serving.trace import TraceConfig, generate_requests
    from repro.serving.workload import bimodal

    lm = BatchLatencyModel(c0=25.0, c1=1.0)
    rs = generate_requests(bimodal(1.0), lm, slo_scale=3.0,
                           cfg=TraceConfig(n_requests=200, seed=5, utilization=0.9))
    res = simulate(rs.fresh(), OrlojScheduler(lm, initial_dists=rs.initial_dists()), ModelExecutor(lm))
    assert read(run_with(res, n_decisions=res.n_decisions)) == res.n_scored / res.n_decisions > 0
