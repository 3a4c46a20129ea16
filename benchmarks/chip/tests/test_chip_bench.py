"""CPU tests of the on-chip benchmark's harness.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

They check the harness's own logic at small sizes: the wall-clock pacing,
the traffic generator, the metric and cost arithmetic, discovery by name,
the names in ``BENCHMARK.json``, the trace reduction on a small trace
recorded on the CPU (``data/cpu_trace.xplane.pb``), the refusal to run
without a TPU, the lower-precision control against each limit, and that a
run whose timed path is broken reads ``correct: false``.  No device number
comes from them.
"""

from __future__ import annotations

import copy
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import pacing  # noqa: E402
import shapes  # noqa: E402
import stats  # noqa: E402
import tracereduce  # noqa: E402
import traffic  # noqa: E402
from peaks import peaks_for  # noqa: E402
from repro.core.eventloop import Worker, run_event_loop  # noqa: E402
from repro.core.request import Request  # noqa: E402
from repro.core.scheduler import Batch  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture(autouse=True)
def _own_compile_cache(tmp_path_factory, monkeypatch):
    # runs here compile for this host's CPU; keep them out of the checkout's cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path_factory.getbasetemp() / "jax_cache"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


# ------------------------------------------------------------------ pacing


class _Fifo:
    """A scheduler that batches everything pending, recording when each hook
    ran on the wall clock against the virtual time it was given."""

    def __init__(self, pacer, stall_ms: float = 0.0) -> None:
        self.q: list = []
        self.pacer = pacer
        self.early: list[float] = []
        self.stall_ms = stall_ms
        self.n_timed_out = 0

    def _seen(self, now):
        self.early.append(now - self.pacer.now_ms())

    def on_arrival(self, req, now):
        self._seen(now)
        self.q.append(req)

    def next_batch(self, now):
        self._seen(now)
        if self.stall_ms:
            time.sleep(self.stall_ms / 1e3)  # the host stalls once
            self.stall_ms = 0.0
        if not self.q:
            return None, None
        b, self.q = self.q, []
        return Batch(b, len(b)), None

    def on_batch_done(self, batch, now, alone):
        self._seen(now)

    @property
    def n_pending(self):
        return len(self.q)


class _FakeExecutor:
    def __init__(self, ms: float) -> None:
        self.ms = ms
        self.measured: list = []

    def __call__(self, batch, now):
        time.sleep(self.ms / 1e3)
        self.measured.append((len(batch.requests), 16, self.ms))
        return self.ms


def _paced_run(stall_ms: float):
    pacer = pacing.Pacer()
    fifo = _Fifo(pacer, stall_ms)
    ex = pacing.PacedExecutor(_FakeExecutor(2.0), pacer)
    reqs = [
        Request("a", release=float(t), slo=50.0, true_time=1.0, payload=np.ones(4, np.int32))
        for t in (5.0, 40.0, 41.0, 80.0)
    ]
    pacer.start()
    res = run_event_loop(reqs, [Worker(pacing.PacedScheduler(fifo, pacer), ex)])
    return reqs, res, fifo, ex


def test_pacing_holds_every_hook_until_its_wall_time():
    reqs, res, fifo, ex = _paced_run(0.0)
    assert res.n_finished_ok == 4
    # no hook ran before the wall clock reached its event's time
    assert max(fifo.early) <= 0.0
    for r in reqs:
        # completion is stamped on the wall clock: at least the batch's 2 ms
        assert r.finished - r.release >= 2.0
    for _, start, end, inner, *_ in ex.batches:
        assert end - start >= inner


def test_a_host_stall_shows_in_latency():
    quiet, _, _, _ = _paced_run(0.0)
    stalled, _, _, ex = _paced_run(30.0)
    # the first batch decision stalls 30 ms of host time; the executor never
    # sees it, but the first request's client-side latency does
    assert stalled[0].finished - stalled[0].release >= 30.0
    assert quiet[0].finished - quiet[0].release < 30.0
    assert ex.batches[0][2] - ex.batches[0][1] >= 30.0


# ----------------------------------------------------------------- traffic


def _mix(name: str) -> dict:
    return traffic.load_mix(harness.mix_path(name))


MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))


@pytest.mark.parametrize("name", MIXES)
def test_traffic_is_reproducible_and_every_seed_gets_the_same_work(name):
    mix = _mix(name)
    a = traffic.plan(mix, 4.0, 2**31 + 5)
    b = traffic.plan(mix, 4.0, 2**31 + 5)
    c = traffic.plan(mix, 4.0, 12345)
    n = traffic.n_requests(mix, 4.0)
    assert n == round(mix["arrivals"]["rate_per_s"] * 4.0)
    for k in ("arrivals_ms", "lengths"):
        np.testing.assert_array_equal(a[k], b[k])
    assert not np.array_equal(a["lengths"], c["lengths"])
    np.testing.assert_array_equal(np.sort(a["lengths"]), np.sort(c["lengths"]))
    def gaps(plan):  # the n + 1 gaps, from the window's start to its end
        return np.sort(np.diff(np.concatenate([[0.0], plan["arrivals_ms"], [4000.0]])))

    np.testing.assert_allclose(gaps(a), gaps(c))
    assert a["arrivals_ms"].min() >= 0.0 and a["arrivals_ms"].max() < 4000.0
    assert np.all(np.diff(a["arrivals_ms"]) >= 0)
    lo, hi = mix["prompt"]["lo"], mix["prompt"]["hi"]
    assert a["lengths"].min() >= lo and a["lengths"].max() <= hi


class _LM:
    def __init__(self, c0, c1):
        self.c0, self.c1 = c0, c1


@pytest.mark.parametrize("name", [c["name"] for c in SPEC["workloads"]])
def test_rate_and_limits_come_from_the_cell_data_not_from_a_measurement(name):
    cell = harness.find(SPEC["workloads"], name, "workload")
    cfg = harness.load_config(SPEC, cell["config"])
    mix = _mix(cell["traffic"])
    runs = []
    for lm, step in ((_LM(1.0, 0.001), 1.0), (_LM(9.0, 0.02), 7.0)):
        s = harness.Setup(cfg, mix, None, None, lm, [], step_ms=step)
        runs.append(harness.make_requests(s, 77, 2.0))
    a, b = runs
    assert [r.release for r in a] == [r.release for r in b]
    assert [r.slo for r in a] == [r.slo for r in b]
    if mix["kind"] == "tokens":
        assert a[0].slo == mix["ttft_ms"] + mix["tpot_ms"] * (a[0].out_tokens - 1)
    else:
        assert {r.slo for r in a} == {float(mix["slo_ms"])}
        assert all(len(r.payload) >= 1 for r in a)


# -------------------------------------------------------------- arithmetic


def _req(release, slo, finished=None, dropped=None, first=None, out=0):
    r = Request("a", release=release, slo=slo, true_time=1.0, out_tokens=out)
    r.finished, r.dropped, r.first_token = finished, dropped, first
    return r


def test_finish_rate_counts_drops_late_and_unfinished_as_misses():
    reqs = [
        _req(0.0, 10.0, finished=5.0),  # ok
        _req(0.0, 10.0, finished=11.0),  # late
        _req(0.0, 10.0, dropped=2.0),  # dropped
        _req(0.0, 10.0),  # never finished
    ]
    assert stats.finish_rate(reqs) == 25.0
    # a token request also needs its first token within the TTFT limit
    tok = [_req(0.0, 50.0, finished=40.0, first=30.0, out=3), _req(0.0, 50.0, finished=40.0, first=5.0, out=3)]
    assert stats.finish_rate(tok, ttft_ms=20.0) == 50.0


def test_a_shed_request_is_a_miss_but_not_a_failure():
    reqs = [
        _req(0.0, 10.0, finished=5.0),  # ok
        _req(0.0, 10.0, finished=11.0),  # late: answered
        _req(0.0, 10.0, dropped=2.0),  # shed by the scheduler
        _req(0.0, 10.0),  # neither served nor shed: lost
    ]
    reqs.append(_req(0.0, 10.0))
    reqs[-1].rejected = 0.0  # shed at admission
    reqs.append(_req(0.0, 10.0))
    reqs[-1].failed = 3.0  # failed in the program
    assert stats.shed(reqs) == 2
    assert stats.failed(reqs) == 2
    assert stats.finish_rate(reqs) == pytest.approx(100.0 / 6)


def test_p95_is_over_served_requests_only():
    reqs = [_req(0.0, 100.0, finished=float(i)) for i in range(1, 101)] + [_req(0.0, 1.0, dropped=0.5)]
    assert stats.latencies(reqs) == [float(i) for i in range(1, 101)]
    assert stats.p95(stats.latencies(reqs)) == pytest.approx(np.percentile(np.arange(1, 101), 95))
    assert stats.p95([]) is None


def test_tpot_is_per_request_and_skips_single_token_answers():
    reqs = [
        _req(0.0, 99.0, finished=30.0, first=10.0, out=5),  # (30-10)/4 = 5
        _req(0.0, 99.0, finished=12.0, first=12.0, out=1),  # one token: no TPOT
        _req(0.0, 99.0, first=3.0, out=4),  # unfinished: no TPOT
    ]
    assert stats.tpots(reqs) == [5.0]


def test_prefill_flops_match_a_hand_count():
    m = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1, "d_ff": 16,
         "vocab_size": 10, "mlp": "swiglu"}
    L = 3  # hd = 4
    per_layer = (
        2 * L * 8 * 2 * 4  # q
        + 2 * 2 * L * 8 * 1 * 4  # k, v
        + 2 * L * 2 * 4 * 8  # o
        + 2 * 2 * 2 * 4 * 6  # q.k and p.v over 3*4/2 = 6 causal pairs
        + 3 * 2 * L * 8 * 16  # gate, up, down
    )
    assert shapes.prefill_flops(m, L) == 2 * per_layer + 2 * 8 * 10
    m_gelu = dict(m, mlp="gelu")
    assert shapes.prefill_flops(m, L) - shapes.prefill_flops(m_gelu, L) == 2 * 2 * L * 8 * 16


def test_decode_attention_cost_matches_a_hand_count():
    m = {"n_layers": 1, "d_model": 8, "n_heads": 2, "n_kv_heads": 1, "d_ff": 16, "vocab_size": 10}
    flops, nbytes = shapes.decode_attention_cost(m, [3, 5])
    assert flops == 2 * 2 * 2 * 4 * 8  # two products, 2 heads x hd 4 x 8 positions
    assert nbytes == 2 * (2 * 2 * 2 * 4 + 2 * 1 * 4 * 8)  # q and out; K and V, bf16


# ------------------------------------------------------ discovery and names


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_every_cell_finds_its_files_by_name(cell):
    cfg = harness.load_config(SPEC, cell["config"])
    assert harness.reference_path(cfg["reference"]).is_file()
    mix = _mix(cell["traffic"])
    if mix["kind"] == "tokens":
        assert harness.reference_path(mix["step_reference"]).is_file()
    for trace in (False, True):
        ms = harness.metrics_for(SPEC, cell["name"], trace)
        assert ms, (cell["name"], trace)
        for m in ms:
            assert harness.reader_path(m["name"]).is_file(), m["name"]


def test_names_and_units_use_only_the_allowed_characters():
    names = [c["name"] for c in SPEC["configs"]] + [w["name"] for w in SPEC["workloads"]]
    names += [w[k] for w in SPEC["workloads"] for k in ("config", "traffic")]
    names += [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += [k for c in SPEC["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    units = [m["unit"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert all(UNIT.match(u) for u in units), units
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert m["better"] in ("lower", "higher")
    for e in SPEC["configs"] + SPEC["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]


def test_every_cell_reports_setup_another_end_to_end_metric_and_a_layer_metric():
    for cell in SPEC["workloads"]:
        e2e = {m["name"] for m in harness.metrics_for(SPEC, cell["name"], False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metrics_for(SPEC, cell["name"], True)


# ------------------------------------------------------------------- trace

TRACE = BENCH / "tests" / "data" / "cpu_trace.xplane.pb"


def test_trace_reduction_on_a_recorded_cpu_trace():
    t = tracereduce.load(TRACE)
    spans = t.host_spans()
    win = [s for s in spans if s[0] == "bench.window"]
    assert len(win) == 1
    lo, hi = win[0][1], win[0][2]
    assert sum(1 for s in spans if s[0] == "bench.execute") == 4
    # on the CPU the XLA operations run on the client's thread: stand in
    # for a device's operation line
    ops = [e for p in t.planes.values() for ln in p if "PjRtCpuClient" in ln.name
           for e in ln.events if not e[0].startswith(("ThreadpoolListener", "end:"))]
    assert ops
    iv = [(a, b) for _, a, b in ops]
    busy = tracereduce.busy_ns(iv, lo, hi)
    gaps = tracereduce.gaps(iv, lo, hi)
    assert 0 < busy < hi - lo
    assert busy + sum(b - a for a, b in gaps) == hi - lo
    idle = tracereduce.attribute(gaps, spans)
    assert sum(idle.values()) == sum(b - a for a, b in gaps)
    # the host slept in pace_wait and sched, with nothing on the "device"
    assert idle["bench.pace_wait"] > idle.get("bench.execute", 0)
    assert idle["bench.sched"] > 0
    by_name = tracereduce.time_by_name(ops, lo, hi)
    assert sum(by_name.values()) >= busy


def test_trace_arithmetic_by_hand():
    iv = [(5, 10), (8, 20), (50, 60)]
    assert tracereduce.union(iv) == [(5, 20), (50, 60)]
    assert tracereduce.busy_ns(iv, 0, 100) == 25
    assert tracereduce.gaps(iv, 0, 100) == [(0, 5), (20, 50), (60, 100)]
    spans = [("w", 0, 100), ("s", 10, 20), ("p", 30, 50), ("e", 60, 90)]
    got = tracereduce.attribute([(0, 15), (40, 70), (95, 120)], spans)
    assert got == {"w": 25, "s": 5, "p": 10, "e": 10, "host:other": 20}


# ------------------------------------------------------- peaks and refusal


def test_peaks_are_keyed_by_device_kind_and_an_unknown_kind_is_an_error():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9 and p["source"]
    with pytest.raises(KeyError):
        peaks_for("cpu")


def test_without_a_tpu_the_command_exits_non_zero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, str(BENCH / "run.py"), "--workload", SPEC["workloads"][0]["name"],
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.lstrip().startswith("{") for line in p.stdout.splitlines())


# --------------------------------------------- control and faults, CPU size


# one mix of each served path, on orloj_gpt cut to a size the CPU runs
PATHS = {"atomic": "gpt-bimodal", "tokens": "gpt-tokens"}


def _tiny(kind: str):
    cell = {"name": f"tiny-{kind}", "config": "orloj_gpt", "traffic": PATHS[kind], "chips": 1}
    cfg = copy.deepcopy(harness.load_config(SPEC, cell["config"]))
    cfg["model"].update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=512)
    mix = copy.deepcopy(_mix(cell["traffic"]))
    if mix["kind"] == "atomic":
        cfg["engine"] = {"buckets": [16, 32, 64], "batch_sizes": [1, 2, 4]}
        mix["prompt"]["hi"] = 64
        mix["prompt"]["lo"] = min(mix["prompt"]["lo"], 8)
        mix["arrivals"]["rate_per_s"], mix["slo_ms"] = 300, 300
        mix["check"]["max_batches"] = 8
    else:
        cfg["engine"] = {"buckets": [16, 32], "batch_sizes": [1, 2, 4, 8]}
        mix["prompt"].update(lo=8, hi=20)
        mix["cache"] = 32
        mix["arrivals"]["rate_per_s"], mix["ttft_ms"], mix["tpot_ms"] = 20, 300, 60
        mix["check"]["step_prob"] = 0.3
    mix["warmup_seconds"] = 0.5
    return cell, cfg, mix


def _run(kind: str, seed: int = 2**31 + 11, control: bool = False):
    import jax

    cell, cfg, mix = _tiny(kind)
    return harness.run_loaded(SPEC, cell, cfg, mix, seed, 1.5, False, time.perf_counter(),
                              control, jax.devices()[:1])


@pytest.mark.parametrize("kind", sorted(PATHS))
def test_program_passes_and_the_lower_precision_control_fails(kind):
    ok = _run(kind)
    assert ok["correct"], ok["checks"]
    ctl = _run(kind, control=True)
    assert not ctl["correct"], ctl["checks"]


def _fault(monkeypatch, fault: str):
    import jax.numpy as jnp
    from repro.models.model import Model
    from repro.serving import engine as eng

    if fault == "answer_altered":
        logits = Model.logits
        monkeypatch.setattr(Model, "logits", lambda self, p, b: logits(self, p, b).at[:, :, 1].add(1e3))
    elif fault == "batch_rows_swapped":
        pad = eng.make_padded_batch

        def swapped(reqs, *a, **k):
            pb = pad(reqs, *a, **k)
            pb.tokens = pb.tokens[::-1].copy()
            return pb

        monkeypatch.setattr(eng, "make_padded_batch", swapped)
    elif fault == "half_batch_left_out":
        logits = Model.logits

        def half(self, p, b):
            out = logits(self, p, b)
            return out.at[out.shape[0] // 2:].set(0.0)

        monkeypatch.setattr(Model, "logits", half)
    elif fault == "state_unchanged":
        impl = eng.DecodeJaxExecutor._step_impl

        def frozen(kc, vc, valid, active, q, nk, nv, *, use_pallas, block_k):
            _, _, _, out = impl(kc, vc, valid, active, q, nk, nv, use_pallas=use_pallas, block_k=block_k)
            return kc, vc, valid, out

        monkeypatch.setattr(eng.DecodeJaxExecutor, "_step_impl", staticmethod(frozen))
    elif fault == "token_answer_altered":
        impl = eng.DecodeJaxExecutor._step_impl

        def wrong(kc, vc, valid, active, q, nk, nv, *, use_pallas, block_k):
            kc2, vc2, valid2, out = impl(kc, vc, valid, active, q, nk, nv, use_pallas=use_pallas, block_k=block_k)
            return kc2, vc2, valid2, out * jnp.float32(1.05)

        monkeypatch.setattr(eng.DecodeJaxExecutor, "_step_impl", staticmethod(wrong))


@pytest.mark.parametrize(
    "fault,kind",
    [
        ("answer_altered", "atomic"),
        ("batch_rows_swapped", "atomic"),
        ("half_batch_left_out", "atomic"),
        ("state_unchanged", "tokens"),
        ("token_answer_altered", "tokens"),
    ],
)
def test_a_broken_timed_path_reads_not_correct(monkeypatch, fault, kind):
    _fault(monkeypatch, fault)
    out = _run(kind)
    assert out["correct"] is False, out["checks"]
