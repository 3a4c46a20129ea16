"""One run of one cell: set-up, the measured window, the drain, the check.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``configs/<config>.json`` (sizes, engine grid, the
name of its plain reference in ``references/``), its traffic mix in
``traffic/<traffic>.json``, and each metric's reader in
``metrics/<metric>.py``.  A new configuration, mix or metric is new files
plus new entries, with no edit here.

The served path is the program's own: ``ServingEngine.serve_pool`` under
``OrlojScheduler`` for ``atomic`` mixes, ``ServingEngine.serve_tokens`` under
``LengthAwareTokenScheduler`` through ``DecodeJaxExecutor`` for ``tokens``
mixes, both on the wall clock through the adapters of ``pacing.py``.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Any

import numpy as np

import pacing
import stats
import traffic
from peaks import peaks_for

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
TRACE_DIR = ROOT / ".bench_trace"


def _load_module(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: no {what} named {name!r}; known: {[e['name'] for e in entries]}")


def load_config(bench: dict, name: str, root: Path = ROOT) -> dict:
    entry = find(bench["configs"], name, "configuration")
    return json.loads((root / entry["file"]).read_text())


def mix_path(name: str) -> Path:
    return BENCH_DIR / "traffic" / f"{name}.json"


def reader_path(metric: str) -> Path:
    return BENCH_DIR / "metrics" / f"{metric}.py"


def reference_path(name: str) -> Path:
    return BENCH_DIR / "references" / f"{name}.py"


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics (``trace`` off) or per-layer metrics
    (``trace`` on): those that list the cell, or that list no cells and move
    an end-to-end metric that the cell reports."""
    e2e = [m for m in bench["end_to_end"] if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [
        m for m in bench["per_layer"]
        if (cell in m["workloads"] if "workloads" in m else m["moves"] in names)
    ]


def require_devices(n: int):
    """The first ``n`` TPU devices; exits (no result printed) otherwise."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        sys.exit(f"benchmark: no TPU: JAX found no backend ({e})")
    if devices[0].platform != "tpu":
        sys.exit(
            f"benchmark: no TPU: JAX's first device is a {devices[0].platform!r} "
            f"device; the benchmark measures only on a TPU"
        )
    if len(devices) < n:
        sys.exit(f"benchmark: the cell needs {n} TPUs, JAX found {len(devices)}")
    return devices[:n]


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else the fixed ``<checkout>/.jax_cache``; every program is
    cached, however quickly it compiled."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


class CompileClock:
    """Programs JAX obtained (compiled, or read from the persistent cache),
    the seconds that took, and how many were read from the cache."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.programs, self.cache_hits


def seed32(seed: int, stream: int) -> int:
    """A 32-bit key for JAX's generator from any non-negative seed."""
    return int(np.random.SeedSequence([int(seed), stream]).generate_state(1)[0])


@dataclasses.dataclass
class Setup:
    cfg: dict
    mix: dict
    engine: Any
    weights: Any
    lm: Any  # the program's Eq.-3 fit (c0, c1), measured in set-up
    executors: list  # the program's JaxExecutor per replica
    decode: Any = None  # DecodeJaxExecutor (tokens mixes)
    step_ms: float = 0.0  # calibrated full-batch decode step (tokens mixes)
    phases: dict = dataclasses.field(default_factory=dict)  # name -> (seconds, compile snapshot)


def program_config(cfg: dict):
    from repro.models.config import ModelConfig

    return ModelConfig(name=cfg["name"], **cfg["model"])


def build(cfg: dict, mix: dict, seed: int, devices, clock: CompileClock, t_start: float) -> Setup:
    """Everything before the window: the engine with the benchmark's
    weights, the program's latency profile, and every shape the window uses."""
    import jax
    from repro.serving.engine import EngineConfig, ServingEngine

    ref = _load_module(reference_path(cfg["reference"]))
    eng_cfg = EngineConfig(
        buckets=tuple(cfg["engine"]["buckets"]), batch_sizes=tuple(cfg["engine"]["batch_sizes"])
    )
    engine = ServingEngine(program_config(cfg), eng_cfg, seed=0)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), engine.params)
    engine.params = engine.executor.params = None  # free the program's own init
    weights = ref.make_weights(cfg["model"], seed32(seed, traffic.STREAM_WEIGHTS))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), weights)
    if jax.tree.structure(got) != jax.tree.structure(want) or jax.tree.leaves(got) != jax.tree.leaves(want):
        raise SystemExit("benchmark: the reference's weights do not match the program's layout")
    engine.params = engine.executor.params = weights
    jax.block_until_ready(weights)
    phases = {"init": (time.perf_counter() - t_start, clock.snapshot())}

    t = time.perf_counter()
    lm = engine.profile_latency_model()
    s = Setup(cfg, mix, engine, weights, lm, [engine.executor])
    if mix["kind"] == "tokens":
        s.decode = engine.decode_executor(max_batch=mix["slots"], max_cache=mix["cache"])
        s.step_ms = s.decode.calibrate()
    phases["profile"] = (time.perf_counter() - t, clock.snapshot())

    t = time.perf_counter()
    if mix["kind"] == "atomic" and mix.get("replicas", 1) > 1:
        s.executors = [engine.executor_for(device=d) for d in devices[: mix["replicas"]]]
        for ex in s.executors:
            if ex is not engine.executor:
                for b in eng_cfg.buckets:
                    for k in sorted(set(eng_cfg.batch_sizes)):
                        ex._run(np.ones((k, b), np.int32))
    if mix["kind"] == "tokens":
        warm_slots(s)
    phases["warm_shapes"] = (time.perf_counter() - t, clock.snapshot())
    s.phases = phases
    return s


def warm_slots(s: Setup) -> None:
    """Each prompt length seeds its cache slot through an eager update that
    compiles per length: run one step for every length the mix can send."""
    from repro.core.request import Request

    lo, hi = s.mix["prompt"]["lo"], s.mix["prompt"]["hi"]
    for n in range(lo, hi + 1):
        r = Request(app_id="warm", release=0.0, slo=1.0, true_time=1.0, prompt_tokens=n, out_tokens=1)
        s.decode.step_time([r], [r], 0.0)


# ---------------------------------------------------------------- traffic


def make_requests(s: Setup, seed: int, seconds: float) -> list:
    """The window's requests, released at their scheduled offsets (ms)."""
    from repro.core.request import Request
    from repro.serving.batcher import bucket_for

    mix = s.mix
    p = traffic.plan(mix, seconds, seed)
    if mix["kind"] == "tokens":
        ttft, tpot = float(mix["ttft_ms"]), float(mix["tpot_ms"])
        return [
            Request(
                app_id="tok", release=float(t), slo=ttft + tpot * (int(o) - 1),
                true_time=float(o) * tpot, prompt_tokens=int(n), out_tokens=int(o),
            )
            for t, n, o in zip(p["arrivals_ms"], p["lengths"], p["outs"])
        ]
    buckets = tuple(s.cfg["engine"]["buckets"])
    lengths = np.minimum(p["lengths"], max(buckets))
    split = traffic.app_threshold(mix)
    toks = traffic.token_ids(lengths, s.cfg["model"]["vocab_size"], seed)
    slo = float(mix["slo_ms"])
    return [
        Request(
            app_id="short" if n <= split else "long", release=float(t), slo=slo,
            true_time=float(bucket_for(int(n), buckets)), payload=tk,
        )
        for t, n, tk in zip(p["arrivals_ms"], lengths, toks)
    ]


def make_schedulers(s: Setup, seed: int) -> list:
    """Fresh program schedulers, primed from the mix's prior stream."""
    from repro.core.distributions import EmpiricalDistribution

    mix = s.mix
    if mix["kind"] == "tokens":
        from repro.core.tokensched import LengthAwareTokenScheduler, TokenSchedConfig

        outs = traffic.prior_lengths(mix, seed, key="output").astype(np.float64)
        tcfg = TokenSchedConfig(
            max_batch=mix["slots"], ttft_slo_ms=float(mix["ttft_ms"]), tpot_slo_ms=float(mix["tpot_ms"]),
            d0=s.step_ms, d1=0.0, prefill_per_token=s.lm.c1,
        )
        prior = {"tok": EmpiricalDistribution.from_samples(outs, n_bins=tcfg.n_bins)}
        return [LengthAwareTokenScheduler(tcfg, initial_len_dists=prior)]
    from repro.launch.serve import make_scheduler
    from repro.serving.batcher import bucket_for

    buckets = tuple(s.cfg["engine"]["buckets"])
    lengths = np.minimum(traffic.prior_lengths(mix, seed), max(buckets))
    sizes = np.array([bucket_for(int(n), buckets) for n in lengths], np.float64)
    split = traffic.app_threshold(mix)
    hist = {"short": sizes[lengths <= split], "long": sizes[lengths > split]}
    bs = tuple(s.cfg["engine"]["batch_sizes"])
    return [make_scheduler("orloj", s.lm, hist, bs) for _ in s.executors]


# ----------------------------------------------------------------- window


@dataclasses.dataclass
class Window:
    requests: list
    result: Any  # the program's SimResult
    pacer: pacing.Pacer
    execs: list  # PacedExecutor per replica, or [PacedDecodeExecutor]
    drained_ms: float  # wall ms from the window's start to the loop's end
    compiles: int  # programs obtained while the loop ran (must be 0)
    n_decisions: int
    gc_ms: list  # the interpreter's garbage-collection pauses while the loop ran


def serve(s: Setup, reqs: list, seed: int, seconds: float, clock: CompileClock,
          annotate=None, on_start=None) -> Window:
    """Offer ``reqs`` open-loop on the wall clock and drain."""
    pacer = pacing.Pacer(annotate)
    scheds = [pacing.PacedScheduler(x, pacer) for x in make_schedulers(s, seed)]
    if s.mix["kind"] == "tokens":
        execs = [pacing.PacedDecodeExecutor(s.decode, pacer)]
    else:
        execs = [pacing.PacedExecutor(ex, pacer, i) for i, ex in enumerate(s.executors)]
    if on_start is not None:
        on_start(execs)
    horizon = seconds * 1e3 + float(s.mix["drain_ms"])
    _, before, _ = clock.snapshot()
    gc_ms: list[float] = []
    gc_t = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_t[0] = time.perf_counter()
        else:
            gc_ms.append((time.perf_counter() - gc_t[0]) * 1e3)

    gc.callbacks.append(on_gc)
    pacer.start()
    with pacer.span("bench.window"):
        if s.mix["kind"] == "tokens":
            res = s.engine.serve_tokens(reqs, scheds[0], execs[0])
        else:
            res = s.engine.serve_pool(
                reqs, scheds, policy=s.mix.get("policy", "least_loaded"), seed=seed % 2**32,
                horizon=horizon, charge_scheduler_overhead=False, executors=execs,
            )
    drained = pacer.now_ms()
    gc.callbacks.remove(on_gc)
    _, after, _ = clock.snapshot()
    return Window(reqs, res, pacer, execs, drained, after - before,
                  res.n_decisions, gc_ms)


# ------------------------------------------------------------------ check


def sampled_requests(reqs: list, ck: dict, seed: int) -> set[int]:
    """Request ids to compare: ``sample`` drawn from the seed, plus the
    ``longest`` longest prompts."""
    rng = traffic.rng_for(seed, traffic.STREAM_CHECK)
    n = len(reqs)
    pick = set(rng.choice(n, size=min(ck["sample"], n), replace=False).tolist())
    order = sorted(range(n), key=lambda i: -len(reqs[i].payload))
    pick.update(order[: ck["longest"]])
    return {reqs[i].rid for i in pick}


def check_atomic(s: Setup, kept: list, sampled: set[int], seed: int, control: bool) -> dict:
    """Widest logit gap, over the sampled served requests, between what the
    timed path produced (the program's own jitted forward, at each request's
    real positions in its padded batch) and the float32 reference run on the
    request's own tokens.  Each position's gap is over all vocabulary
    entries, in units of the reference logits' RMS at that position."""
    import jax
    import jax.numpy as jnp

    ref = _load_module(reference_path(s.cfg["reference"]))
    fwd = ref.make_forward(s.cfg["model"], "f32")
    ctl = ref.make_forward(s.cfg["model"], "fp8") if control else None
    pad_to = max(s.cfg["engine"]["buckets"])
    vocab = s.cfg["model"]["vocab_size"]
    rng = traffic.rng_for(seed, traffic.STREAM_CHECK + 10)
    dev = jax.tree.leaves(s.weights)[0].devices().pop()

    @jax.jit
    def gap(out, i, want, n):
        # one program per executed shape, whatever the request's length
        got = jax.lax.dynamic_index_in_dim(out, i, keepdims=False).astype(jnp.float32)
        w = want[: got.shape[0]]
        rms = jnp.sqrt(jnp.mean(w * w, axis=-1, keepdims=True))
        real = (jnp.arange(got.shape[0]) < n)[:, None]
        return jnp.max(jnp.where(real, jnp.abs(got - w) / rms, 0.0))

    worst, n, positions = 0.0, 0, 0
    for reqs, _args, out in kept:
        out = jax.device_put(out, dev)
        for i, r in enumerate(reqs):
            if r.rid not in sampled or r.finished is None:
                continue
            L = len(r.payload)
            # pad with other tokens than the batcher's zeros: a real
            # position that saw the padding would then differ
            toks = np.concatenate([r.payload, rng.integers(1, vocab, pad_to - L)]).astype(np.int32)
            want = fwd(s.weights, toks)
            if control:
                g = gap(ctl(s.weights, toks)[None], 0, want, L)
            else:
                g = gap(out, i, want, L)
            worst = max(worst, float(g))
            n += 1
            positions += L
    return {"numbers": {"logit_gap": worst}, "compared": n, "positions": positions}


def check_tokens(s: Setup, kept: list, control: bool) -> dict:
    """Each sampled decode step of the window against the plain step:
    the cache write and the valid lengths exactly, the attention output by
    its widest gap in units of the reference output's RMS per slot; and the
    valid lengths against what each active request's prompt and tokens so
    far imply."""
    ref = _load_module(reference_path(s.mix["step_reference"]))
    worst, cache_bad, valid_bad = 0.0, 0, 0
    for (_, expect), args, out in kept:
        kc, vc, valid, active, q, nk, nv = (np.asarray(a) for a in args)
        kc2, vc2, valid2, o = (np.asarray(a) for a in out)
        rk, rv, rvalid, ro = ref.step(kc, vc, valid, active, q, nk, nv)
        if control:
            o = ref.step(kc, vc, valid, active, q, nk, nv, precision="fp8")[3]
        cache_bad += int(np.sum(kc2 != rk) + np.sum(vc2 != rv))
        valid_bad += int(np.sum(valid2 != rvalid))
        valid_bad += int(sorted(valid2[valid2 > 0].tolist()) != sorted(expect))
        live = rvalid > 0
        if live.any():
            rms = np.sqrt(np.mean(ro[live] ** 2, axis=(-1, -2), keepdims=True))
            worst = max(worst, float(np.max(np.abs(o[live] - ro[live]) / rms)))
    return {
        "numbers": {"attn_gap": worst, "cache_mismatch": cache_bad, "valid_mismatch": valid_bad},
        "compared": len(kept), "positions": 0,
    }


# ------------------------------------------------------------------- trace


@dataclasses.dataclass
class TraceView:
    """What the readers take from the trace, over the traced window."""

    window_ns: int
    busy_ns: dict  # device plane -> ns with an operation running
    ops_ns: dict  # device plane -> {op name: ns}
    modules_ns: dict  # device plane -> {program name: ns}
    idle_by_host: dict  # host span name -> idle ns, summed over devices

    def module_ns(self, match) -> int:
        return sum(v for d in self.modules_ns.values() for k, v in d.items() if match(k))

    def op_ns(self, match) -> int:
        return sum(v for d in self.ops_ns.values() for k, v in d.items() if match(k))


def reduce_trace(path: Path, n_devices: int) -> TraceView:
    import tracereduce as tr

    t = tr.load(path)
    spans = t.host_spans()
    win = [sp for sp in spans if sp[0] == "bench.window"]
    if not win:
        raise RuntimeError("trace holds no bench.window span")
    lo, hi = win[0][1], win[0][2]
    devs = sorted(p for p in t.planes if p.startswith("/device:TPU:"))[:n_devices]
    busy, ops, mods, idle = {}, {}, {}, {}
    for p in devs:
        lines = {ln.name: ln for ln in t.planes[p]}
        op_line = lines.get("XLA Ops")
        evs = op_line.events if op_line else []
        busy[p] = tr.busy_ns([(a, b) for _, a, b in evs], lo, hi)
        ops[p] = tr.time_by_name(evs, lo, hi)
        mods[p] = tr.time_by_name(lines["XLA Modules"].events, lo, hi) if "XLA Modules" in lines else {}
        for k, v in tr.attribute(tr.gaps([(a, b) for _, a, b in evs], lo, hi), spans).items():
            idle[k] = idle.get(k, 0) + v
    return TraceView(hi - lo, busy, ops, mods, idle)


# --------------------------------------------------------------------- run


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""

    cell: dict
    cfg: dict
    mix: dict
    setup_s: float
    window: Window
    peaks: dict
    trace: TraceView | None

    @property
    def model(self) -> dict:
        return self.cfg["model"]

    @property
    def kind(self) -> str:
        return self.mix["kind"]

    @property
    def batches(self) -> list:
        return [b for ex in self.window.execs for b in getattr(ex, "batches", [])]

    @property
    def steps(self) -> list:
        return [st for ex in self.window.execs for st in getattr(ex, "steps", [])]


def read_metrics(bench: dict, run: Run, trace: bool) -> dict:
    out = {}
    for m in metrics_for(bench, run.cell["name"], trace):
        value = _load_module(reader_path(m["name"])).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def log(*a) -> None:
    print(*a, flush=True)


def prepare(cfg: dict, mix: dict, seed: int, devices, t_start: float) -> tuple[Setup, CompileClock]:
    """Set-up up to the window: build, then the served path end to end on a
    warm-up stream of its own."""
    import jax

    dev = devices[0]
    log(f"device: {dev.device_kind} ({dev.platform}), {len(jax.devices())} visible, cell uses {len(devices)}")
    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    s = build(cfg, mix, seed, devices, clock, t_start)
    t = time.perf_counter()
    warm = make_requests(s, seed + 7919, mix["warmup_seconds"])  # a stream the window never sees
    serve(s, warm, seed, mix["warmup_seconds"], clock)
    s.phases["warm_serve"] = (time.perf_counter() - t, clock.snapshot())
    return s, clock


def run_cell(workload: str, seed: int, seconds: float, trace: bool, t_start: float,
             control: bool = False) -> dict:
    bench = load_benchmark()
    cell = find(bench["workloads"], workload, "workload")
    cfg = load_config(bench, cell["config"])
    mix = traffic.load_mix(mix_path(cell["traffic"]))
    devices = require_devices(cell["chips"])
    return run_loaded(bench, cell, cfg, mix, seed, seconds, trace, t_start, control, devices)


def run_loaded(bench: dict, cell: dict, cfg: dict, mix: dict, seed: int, seconds: float,
               trace: bool, t_start: float, control: bool, devices) -> dict:
    """One run of ``cell`` with its configuration and mix already loaded,
    on ``devices``; returns the result line's object."""
    import jax

    dev = devices[0]
    peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" else {}
    s, clock = prepare(cfg, mix, seed, devices, t_start)

    reqs = make_requests(s, seed, seconds)
    ck = mix["check"]
    holder: dict = {}

    def on_start(execs):
        if mix["kind"] == "tokens":
            take = traffic.rng_for(seed, traffic.STREAM_CHECK).random(1 << 17) < ck["step_prob"]
            ex = execs[0]
            cap = pacing.Capture(s.decode._step, lambda: (
                ex.current if ex.current is not None and ex.current[0] < take.size
                and take[ex.current[0]] and len(cap.kept) < ck["max_steps"] else None))
            s.decode._step = cap
            holder["caps"] = [(s.decode, "_step", cap)]
        else:
            sampled = sampled_requests(reqs, ck, seed)
            holder["sampled"] = sampled
            caps = []
            for ex, inner in zip(execs, s.executors):
                def keep(ex=ex):
                    b = ex.current
                    if b is None or sum(len(c.kept) for _, _, c in caps) >= ck["max_batches"]:
                        return None
                    return list(b.requests) if any(r.rid in sampled for r in b.requests) else None
                cap = pacing.Capture(inner._fwd, keep)
                inner._fwd = cap
                caps.append((inner, "_fwd", cap))
            holder["caps"] = caps

    annotate = None
    if trace:
        annotate = jax.profiler.TraceAnnotation
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # host spans are the benchmark's own annotations (host tracer level 1);
        # the Python tracer would slow every host call it measures
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    w = serve(s, reqs, seed, seconds, clock, annotate=annotate, on_start=on_start)
    if trace:
        jax.profiler.stop_trace()
    for obj, attr, cap in holder["caps"]:
        setattr(obj, attr, cap.fn)
    kept = [k for _, _, cap in holder["caps"] for k in cap.kept]

    mem = max(d.memory_stats().get("peak_bytes_in_use", 0) for d in devices) if dev.platform == "tpu" else 0

    # set-up accounting
    prev = (0.0, 0, 0)
    for name, (secs, snap) in s.phases.items():
        log(f"setup [{name}]: {secs!r} s; {snap[1] - prev[1]} programs in {snap[0] - prev[0]!r} s, "
            f"{(snap[1] - prev[1]) - (snap[2] - prev[2])} compiled, {snap[2] - prev[2]} read from the cache")
        prev = snap
    log(f"setup_s: {setup_s!r} (latency model c0={s.lm.c0!r} ms, c1={s.lm.c1!r} ms/token"
        + (f", decode step {s.step_ms!r} ms" if s.step_ms else "") + ")")
    log(f"compiles in window: {w.compiles}")
    late = w.pacer.arrival_late_ms
    log(f"arrivals handled late (ms after due): p50={pacing.percentile(late, 50)!r} "
        f"p99={pacing.percentile(late, 99)!r} max={max(late) if late else float('nan')!r} over {len(late)}")
    stalls = ", ".join(f"{k} {len(v)} x (max {max(v):.1f} ms, total {sum(v):.1f} ms)"
                       for k, v in sorted(w.pacer.stalls.items()))
    log(f"host stalls over {pacing.STALL_MS} ms: {stalls or 'none'}")
    log(f"garbage collection in window: {len(w.gc_ms)} pauses, total {sum(w.gc_ms):.1f} ms, "
        f"max {max(w.gc_ms, default=0.0):.1f} ms")
    res = w.result
    log(f"window: {len(reqs)} released over {seconds} s; ok={res.n_finished_ok} late={res.n_finished_late} "
        f"dropped={res.n_dropped} unserved={res.n_unserved} batches={res.n_batches}; "
        f"loop ended {w.drained_ms - seconds * 1e3!r} ms after the window")

    tview = None
    if trace:
        import tracereduce

        tview = reduce_trace(tracereduce.find_xplane(TRACE_DIR), len(devices))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    run = Run(cell, cfg, mix, setup_s, w, peaks, tview)
    metrics = read_metrics(bench, run, trace)

    # the check, once the window has closed and the program's state is freed
    s.executors = None
    s.engine.executor = None
    s.engine._device_executors.clear()
    t = time.perf_counter()
    if mix["kind"] == "tokens":
        chk = check_tokens(s, kept, control)
    else:
        chk = check_atomic(s, kept, holder["sampled"], seed, control)
    del kept
    limits = ck["limits"]
    numbers = {k: {"value": v, "limit": limits[k]} for k, v in chk["numbers"].items()}
    correct = chk["compared"] > 0 and all(v["value"] <= v["limit"] for v in numbers.values())
    log(f"check: compared {chk['compared']} ({chk['positions']} positions) in {time.perf_counter() - t!r} s")

    lost = stats.failed(reqs)
    log(f"outcome: {len(reqs)} released, {stats.shed(reqs)} shed by the scheduler (misses in "
        f"finish_rate), {lost} failed or left neither served nor shed")
    out = {
        "correct": bool(correct),
        "attempted": len(reqs),
        "failed": lost,
        "metrics": metrics,
        "device": {
            "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
            "memory_peak_bytes": int(mem),
        },
    }
    if tview is not None:
        out["device"]["busy_s"] = sum(tview.busy_ns.values()) / max(len(tview.busy_ns), 1) / 1e9
        out["device"]["window_s"] = tview.window_ns / 1e9
        ops: dict = {}
        for d in tview.ops_ns.values():
            for k, v in d.items():
                ops[k] = ops.get(k, 0) + v
        out["breakdown"] = {
            "device_ops": [[k, v / 1e9] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[k, v / 1e9] for k, v in sorted(tview.idle_by_host.items(), key=lambda kv: -kv[1])[:10]],
        }
    out["checks"] = numbers
    return out
