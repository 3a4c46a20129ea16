"""The program's own spans (``orloj.*``, :mod:`repro.tracing`) and the
scheduler's scoring counters.

Spans are read back from a JAX profiler trace recorded on the CPU: each
executed batch shows its executor phases once, in order; the scheduler's
phase spans lie inside its hook spans; recording changes no decision.
The counters agree between the two event-loop engines, and the simulator
still runs without importing JAX.
"""

from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import (
    BatchLatencyModel,
    EDFScheduler,
    EmpiricalDistribution,
    ModelExecutor,
    MultiModelOrlojScheduler,
    OrlojScheduler,
    SchedulerConfig,
    Worker,
    run_event_loop,
    simulate,
)
from repro.models.config import ModelConfig
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.trace import TraceConfig, generate_requests
from repro.serving.workload import bimodal
from repro.tracing import span

SRC = Path(__file__).resolve().parents[1] / "src"
LM = BatchLatencyModel(c0=25.0, c1=1.0)

TINY = ModelConfig(
    name="tiny", arch_type="dense", n_layers=1, d_model=32, n_heads=2,
    n_kv_heads=2, d_ff=64, vocab_size=128, dtype="float32", scan_layers=False,
)

EXEC_PHASES = ("orloj.exec.pad", "orloj.exec.put", "orloj.exec.dispatch", "orloj.exec.wait")
SCHED_HOOKS = ("orloj.sched.on_arrivals", "orloj.sched.next_batch", "orloj.sched.on_batch_done")
SCHED_PHASES = ("orloj.sched.rescore", "orloj.sched.drop", "orloj.sched.pop", "orloj.sched.recompute")


def _traced(tmp_path: Path, fn):
    """Run ``fn`` under a JAX profiler trace; returns ``(fn(), spans)`` with
    the host spans named ``orloj.*`` or ``test.*`` as ``(name, start_ns,
    end_ns)``, sorted by start."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (xplane,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    spans = []
    for plane in jax.profiler.ProfileData.from_file(str(xplane)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("orloj.", "test.")):
                    spans.append((e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)))
    return out, sorted(spans, key=lambda sp: (sp[1], -sp[2]))


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _rs(n=500, seed=11, util=0.9):
    return generate_requests(
        bimodal(1.0), LM, slo_scale=3.0,
        cfg=TraceConfig(n_requests=n, seed=seed, utilization=util),
    )


def _orloj(rs, **cfg):
    return OrlojScheduler(LM, cfg=SchedulerConfig(**cfg) if cfg else None,
                          initial_dists=rs.initial_dists())


# ---------------------------------------------------------------- helper


def test_span_is_a_trace_annotation_once_jax_is_imported():
    s = span("orloj.test.one")
    assert isinstance(s, jax.profiler.TraceAnnotation)
    with s:
        pass


def test_simulator_runs_without_importing_jax():
    code = (
        "import sys\n"
        "import numpy as np\n"
        "from repro.core import (BatchLatencyModel, EmpiricalDistribution, ModelExecutor,\n"
        "    OrlojScheduler, Request, simulate)\n"
        "from repro.tracing import span\n"
        "rng = np.random.default_rng(3)\n"
        "lm = BatchLatencyModel(c0=25.0, c1=1.0)\n"
        "sizes = rng.choice([10.0, 60.0], size=200)\n"
        "arrivals = np.cumsum(rng.exponential(20.0, size=200))\n"
        "reqs = [Request('s' if x < 30 else 'l', release=float(t), slo=300.0, true_time=float(x))\n"
        "        for t, x in zip(arrivals, sizes)]\n"
        "dists = {a: EmpiricalDistribution.from_samples(np.array([v - 1, v, v + 1]))\n"
        "         for a, v in (('s', 10.0), ('l', 60.0))}\n"
        "s = OrlojScheduler(lm, initial_dists=dists)\n"
        "res = simulate(reqs, s, ModelExecutor(lm))\n"
        "assert res.n_scored == s.n_scored > 0, (res.n_scored, s.n_scored)\n"
        "assert span('orloj.x') is span('orloj.y')\n"
        "print('jax' in sys.modules)\n"
    )
    env = dict(os.environ, PYTHONPATH=str(SRC))
    p = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "False"


# -------------------------------------------------------------- executor


class _BatchSpan:
    """Runs the executor inside a ``test.batch`` span and records the
    executed shape of each batch."""

    def __init__(self, inner) -> None:
        self.inner = inner
        self.shapes: list[tuple[int, int]] = []

    def __call__(self, batch, now):
        with jax.profiler.TraceAnnotation("test.batch"):
            ms = self.inner(batch, now)
        k_pad, bucket, _ = self.inner.measured[-1]
        self.shapes.append((k_pad, bucket))
        return ms


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A tiny model served under Orloj with the profiler on.  No latency
    profile is taken first, so every shape compiles inside the trace."""
    eng = ServingEngine(TINY, EngineConfig(buckets=(8, 16), batch_sizes=(1, 2, 4), profile_reps=1))
    lm = BatchLatencyModel(c0=1.0, c1=0.01)
    reqs, hist = eng.make_requests(
        40, lm, length_sampler=lambda rng: int(rng.integers(2, 17)),
        slo_scale=200.0, utilization=0.6, seed=5,
    )
    dists = {a: EmpiricalDistribution.from_samples(x) for a, x in hist.items() if len(x) >= 2}
    sched = OrlojScheduler(lm, cfg=SchedulerConfig(batch_sizes=(1, 2, 4)), initial_dists=dists)
    ex = _BatchSpan(eng.executor)
    eng.executor = ex
    res, spans = _traced(tmp_path_factory.mktemp("trace"), lambda: eng.serve(reqs, sched))
    return res, spans, ex.shapes


def test_every_batch_shows_each_executor_phase_once_in_order(served):
    res, spans, shapes = served
    batches = [sp for sp in spans if sp[0] == "test.batch"]
    assert len(batches) == res.n_batches == len(shapes) > 0
    seen: set[tuple[int, int]] = set()
    for b, shape in zip(batches, shapes):
        inner = [sp for sp in spans if sp[0].startswith("orloj.exec.") and _inside(sp, b)]
        names = [sp[0] for sp in inner]
        new = shape not in seen
        seen.add(shape)
        want = list(EXEC_PHASES)
        if new:  # the warm-up call of a new shape sits between put and dispatch
            want.insert(2, "orloj.exec.compile")
        assert names == want, (shape, names)
        for a, c in zip(inner, inner[1:]):
            assert a[2] <= c[1], (a, c)  # one after the other, none overlapping
    assert len(seen) > 1
    n_exec = sum(1 for sp in spans if sp[0].startswith("orloj.exec."))
    assert n_exec == len(EXEC_PHASES) * len(batches) + len(seen)


def test_weights_are_cast_once_per_assignment_and_never_in_a_batch(tmp_path):
    eng = ServingEngine(TINY, EngineConfig(buckets=(8, 16), batch_sizes=(1, 2), profile_reps=1))
    ex = eng.executor
    lm = BatchLatencyModel(c0=1.0, c1=0.01)
    reqs, hist = eng.make_requests(
        20, lm, length_sampler=lambda rng: int(rng.integers(2, 17)),
        slo_scale=200.0, utilization=0.6, seed=6,
    )
    dists = {a: EmpiricalDistribution.from_samples(x) for a, x in hist.items() if len(x) >= 2}
    sched = OrlojScheduler(lm, cfg=SchedulerConfig(batch_sizes=(1, 2)), initial_dists=dists)
    eng.executor = _BatchSpan(ex)
    weights = ex.params

    def run():
        ex.params = None  # no copy to make
        ex.params = weights
        res = eng.serve(reqs, sched)
        ex.params = weights
        return res

    res, spans = _traced(tmp_path, run)
    casts = [sp for sp in spans if sp[0] == "orloj.exec.cast"]
    batches = [sp for sp in spans if sp[0] == "test.batch"]
    assert len(casts) == 2 and ex.n_weight_casts == 3  # the third made the executor
    assert len(batches) == res.n_batches > 0
    for c in casts:
        assert not any(b[1] < c[2] and c[1] < b[2] for b in batches), c


def test_scheduler_phase_spans_nest_inside_its_hook_spans(served):
    _, spans, _ = served
    hooks = [sp for sp in spans if sp[0] in SCHED_HOOKS]
    phases = [sp for sp in spans if sp[0] in SCHED_PHASES]
    assert {sp[0] for sp in hooks} == set(SCHED_HOOKS)
    assert {"orloj.sched.drop", "orloj.sched.pop", "orloj.sched.rescore"} <= {sp[0] for sp in phases}
    for ph in phases:
        assert any(_inside(ph, h) for h in hooks), ph
    # hooks never nest in one another, nor in an executor call
    for h in hooks:
        assert not any(o is not h and _inside(h, o) for o in hooks), h
        assert not any(_inside(h, b) for b in spans if b[0] == "test.batch"), h


def test_snapshot_swaps_rebuild_and_recompute_inside_batch_feedback(tmp_path):
    rs = _rs()
    sched = _orloj(rs)
    res, spans = _traced(tmp_path, lambda: simulate(rs.fresh(), sched, ModelExecutor(LM)))
    done = [sp for sp in spans if sp[0] == "orloj.sched.on_batch_done"]
    hooks = [sp for sp in spans if sp[0] in SCHED_HOOKS]
    rebuilds = [sp for sp in spans if sp[0] == "orloj.sched.rebuild"]
    recomputes = [sp for sp in spans if sp[0] == "orloj.sched.recompute"]
    assert rebuilds and recomputes
    for sp in rebuilds:
        assert any(_inside(sp, d) for d in done), sp
    for sp in recomputes:
        assert any(_inside(sp, h) for h in hooks), sp
    assert sum(1 for sp in spans if sp[0] == "orloj.sched.next_batch") == res.n_decisions


def test_each_decode_step_shows_its_phases_once(tmp_path):
    from repro.core import Request

    eng = ServingEngine(TINY, EngineConfig(buckets=(8, 16), batch_sizes=(1, 2, 4), profile_reps=1))
    dec = eng.decode_executor(max_batch=4, max_cache=16, use_pallas=False)
    reqs = [Request("tok", release=0.0, slo=1e3, true_time=1.0, prompt_tokens=n, out_tokens=4)
            for n in (5, 9, 3)]

    def steps():
        for active, joined in ((reqs[:2], reqs[:2]), (reqs, reqs[2:]), (reqs[1:], [])):
            with jax.profiler.TraceAnnotation("test.step"):
                dec.step_time(active, joined, 0.0)

    _, spans = _traced(tmp_path, steps)
    phases = ("orloj.decode.release", "orloj.decode.seed", "orloj.decode.values",
              "orloj.decode.dispatch", "orloj.decode.wait")
    for step, joins in zip((sp for sp in spans if sp[0] == "test.step"), (True, True, False)):
        inner = [sp[0] for sp in spans if _inside(sp, step) and sp[0].startswith("orloj.")]
        decode = [n for n in inner if n.startswith("orloj.decode.")]
        if joins:  # the joined prompts' prefill, then their cache slots
            assert decode == list(phases), decode
            assert [n for n in EXEC_PHASES if n in inner] == list(EXEC_PHASES)
        else:
            assert decode == [p for p in phases if p != "orloj.decode.seed"], decode
            assert not any(n.startswith("orloj.exec.") for n in inner)


# ------------------------------------------------------------ determinism


class _Recorder:
    """An Eq.-3 executor that records each batch as positions in the
    request list (ids differ between two fresh copies of a trace)."""

    def __init__(self, reqs) -> None:
        self.inner = ModelExecutor(LM, jitter=0.05, seed=3)
        self.index = {r.rid: i for i, r in enumerate(reqs)}
        self.batches: list[tuple[float, tuple[int, ...]]] = []

    def __call__(self, batch, now):
        self.batches.append((now, tuple(self.index[r.rid] for r in batch.requests)))
        return self.inner(batch, now)


def _decisions(rs):
    reqs = rs.fresh()
    ex = _Recorder(reqs)
    sched = _orloj(rs)
    res = simulate(reqs, sched, ex)
    return res, ex.batches, sched.n_scored


def test_a_seeded_run_decides_the_same_with_and_without_the_profiler(tmp_path):
    rs = _rs(n=400, seed=21)
    plain = _decisions(rs)
    traced, spans = _traced(tmp_path, lambda: _decisions(rs))
    assert spans  # the profiler was recording
    (a, a_batches, a_scored), (b, b_batches, b_scored) = plain, traced
    assert a_batches == b_batches
    assert a_scored == b_scored
    # every field but the measured wall time inside the hooks
    fa, fb = dataclasses.asdict(a), dataclasses.asdict(b)
    for f in ("sched_time_ms", "latencies"):
        fa.pop(f), fb.pop(f)
    assert fa == fb
    assert a.latencies.tobytes() == b.latencies.tobytes()


# --------------------------------------------------------------- counters


@pytest.mark.parametrize("n_workers,policy", [(1, "round_robin"), (3, "p2c")])
def test_n_scored_is_the_same_in_both_engines_and_is_the_schedulers_count(n_workers, policy):
    rs = _rs(util=0.9 * n_workers)
    got = {}
    for engine in ("scalar", "array"):
        scheds = [_orloj(rs) for _ in range(n_workers)]
        workers = [Worker(s, ModelExecutor(LM, seed=i)) for i, s in enumerate(scheds)]
        res = run_event_loop(rs.fresh(), workers, policy=policy, engine=engine)
        assert res.n_scored == sum(s.n_scored for s in scheds)
        got[engine] = (res.n_scored, [s.n_scored for s in scheds])
    assert got["scalar"] == got["array"]
    n_bs = len(SchedulerConfig().batch_sizes)
    # every arrival is scored at every batch size, at least once
    assert got["scalar"][0] >= len(rs.fresh()) * n_bs


def test_n_scored_counts_arrivals_rescores_and_recomputes():
    rs = _rs(n=50, seed=4)
    reqs = rs.fresh()
    sched = _orloj(rs, batch_sizes=(1, 2, 4))
    sched.on_arrivals(reqs[:10], reqs[9].release)
    assert sched.n_scored == 10 * 3
    sched._recompute_all(reqs[9].release)
    assert sched.n_scored == 2 * 10 * 3
    before = sched.n_scored
    sched._update_due_scores(float("inf"))  # every milestone falls due
    assert sched.n_scored > before


def test_n_scored_is_counted_once_per_scheduler_and_zero_without_the_counter():
    rs = _rs(n=200, seed=9)
    shared = _orloj(rs)
    res = run_event_loop(rs.fresh(), [Worker(shared, ModelExecutor(LM)) for _ in range(2)],
                         policy="round_robin")
    assert res.n_scored == shared.n_scored > 0
    res = simulate(rs.fresh(), EDFScheduler(LM), ModelExecutor(LM))
    assert res.n_scored == 0 and res.n_decisions > 0


def test_the_multi_model_facade_sums_its_inner_counters():
    rs = _rs(n=200, seed=13)
    reqs = rs.fresh()
    for i, r in enumerate(reqs):
        r.model_id = "a" if i % 2 else "b"
    dists = rs.initial_dists()
    multi = MultiModelOrlojScheduler(LM, {"a": dists, "b": dists})
    res = simulate(reqs, multi, ModelExecutor(LM))
    inner = list(multi._inner.values())
    assert all(s.n_scored > 0 for s in inner)
    assert res.n_scored == multi.n_scored == sum(s.n_scored for s in inner)
    assert np.isfinite(res.latencies).all()
