"""Serve full-width ``orloj_gpt`` on a TPU, end to end, and check what comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the replica pool only

One chip, in order:

1. prefill under Orloj: ``ServingEngine`` profiles its Eq.-3 latency curve
   over every (batch, bucket) shape, then serves length-skewed requests
   under ``OrlojScheduler`` through ``engine.serve``; the prefill logits
   must be finite;
2. token decode: the decode executor must run the compiled Pallas kernel
   (``use_pallas`` and ``tpu_custom_call`` in its lowered step), then
   serves token requests under ``LengthAwareTokenScheduler``;
3. kernel numerics: one call each of the compiled ``decode_attention`` and
   ``flash_attention`` against the jnp references of ``kernels/ref.py``.

``--chips 4`` serves one trace through a pool of four Orloj replicas, each
on its own chip, then the same trace through four replicas on one chip, and
checks that every chip ran batches and that the request counts agree.

Everything runs in this one process: a chip belongs to one process at a
time.  With no TPU the script exits non-zero and prints no result.  Any
failed check exits non-zero.  The last line of standard output is one JSON
object naming the device.  The compile cache is JAX_COMPILATION_CACHE_DIR
where that is set, and ``.jax_cache/`` at the root of the checkout
otherwise.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.orloj_gpt import SERVE_BATCH_SIZES, SERVE_BUCKETS  # noqa: E402
from repro.core.tokensched import LengthAwareTokenScheduler, TokenSchedConfig  # noqa: E402
from repro.kernels import decode_attention, flash_attention, ref  # noqa: E402
from repro.launch.serve import bimodal_length, make_scheduler  # noqa: E402
from repro.serving.engine import EngineConfig, ServingEngine  # noqa: E402


def require_tpu(n_chips: int) -> list[jax.Device]:
    """The devices to run on; exits (no result printed) without a TPU."""
    try:
        devices = jax.devices()
    except RuntimeError as e:
        sys.exit(f"chip_smoke: no TPU: JAX found no backend ({e})")
    if devices[0].platform != "tpu":
        sys.exit(
            f"chip_smoke: no TPU: JAX's first device is a "
            f"{devices[0].platform!r} device; this script runs only on a TPU"
        )
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} needs {n_chips} TPUs, found {len(devices)}")
    return devices[:n_chips]

# Kernel against reference, elementwise: |out - ref| <= ATOL + RTOL * |ref|.
# The bf16 tolerance of tests/test_kernels.py; a wrong mask or tile is off
# by O(0.1-1) on these unit-variance inputs.
ATOL = RTOL = 2e-2


class CompileClock:
    """Seconds JAX spent getting compiled programs (compiling, or reading
    them from the persistent cache), and how many came from the cache."""

    def __init__(self) -> None:
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

    def report(self, phase: str) -> None:
        print(
            f"compile [{phase}]: {self.seconds!r} s over {self.programs} "
            f"programs, {self.cache_hits} read from the compile cache"
        )


def check(ok: bool, what: str) -> None:
    if not ok:
        sys.exit(f"chip_smoke: FAILED: {what}")
    print(f"ok: {what}")


def report(path: str, res) -> int:
    served = res.n_finished_ok + res.n_finished_late
    print(
        f"{path}: requests={res.n_total} served={served} "
        f"finished_in_slo={res.n_finished_ok} late={res.n_finished_late} "
        f"dropped={res.n_dropped} unserved={res.n_unserved} batches={res.n_batches}"
    )
    check(res.conserved, f"{path}: every request reached one terminal state")
    return served


def build_engine() -> tuple[ServingEngine, object]:
    cfg = get_config("orloj_gpt")  # full published width, not .reduced()
    engine = ServingEngine(
        cfg, EngineConfig(buckets=SERVE_BUCKETS, batch_sizes=SERVE_BATCH_SIZES)
    )
    n_params = engine.model.param_count(engine.params)
    print(
        f"model: {cfg.name} {n_params} params, {cfg.n_layers} layers, "
        f"d_model={cfg.d_model}, vocab={cfg.vocab_size}, dtype={cfg.dtype}"
    )
    t0 = time.perf_counter()
    lm = engine.profile_latency_model()
    print(
        f"profile: c0={lm.c0!r} ms c1={lm.c1!r} ms/token over "
        f"{len(SERVE_BUCKETS) * len(SERVE_BATCH_SIZES)} (batch, bucket) shapes "
        f"({time.perf_counter() - t0!r} s, compiles included)"
    )
    return engine, lm


def prefill_phase(engine: ServingEngine, lm) -> None:
    reqs, hist = engine.make_requests(64, lm, length_sampler=bimodal_length, seed=0)
    sched = make_scheduler("orloj", lm, hist, engine.cfg.batch_sizes)
    served = report("prefill/orloj", engine.serve(reqs, sched))
    check(served > 0, "prefill/orloj served requests")

    k, s = max(SERVE_BATCH_SIZES), max(SERVE_BUCKETS)
    vocab = engine.model.cfg.vocab_size
    toks = np.random.default_rng(0).integers(1, vocab, size=(k, s), dtype=np.int32)
    logits = jax.jit(engine.model.logits)(engine.params, {"tokens": jnp.asarray(toks)})
    check(logits.shape == (k, s, vocab), f"prefill logits have shape {(k, s, vocab)}")
    check(bool(jnp.isfinite(logits).all()), "prefill logits are finite")


def decode_phase(engine: ServingEngine, lm) -> None:
    dec = engine.decode_executor(max_batch=8, max_cache=256)
    check(dec.use_pallas, "decode executor runs the Pallas kernel")
    check(
        "tpu_custom_call" in dec.lower_step().as_text(),
        "decode step lowers to a compiled kernel (tpu_custom_call)",
    )
    step_ms = dec.calibrate()
    print(f"decode: full-batch step {step_ms!r} ms (8 slots x 256 cache)")
    # TTFT leaves room for one full prefill batch, which joins run inside
    # a decode step; the engine's default of 8 TPOTs assumes prefill is cheap.
    tpot_ms = 2.0 * step_ms
    prefill_ms = lm.c0 + lm.c1 * max(SERVE_BATCH_SIZES) * 128
    ttft_mult = 8.0 + 2.0 * prefill_ms / tpot_ms
    reqs = engine.make_token_requests(32, dec, ttft_mult=ttft_mult, seed=0)
    cfg = TokenSchedConfig(
        max_batch=dec.max_batch,
        ttft_slo_ms=ttft_mult * tpot_ms,
        tpot_slo_ms=tpot_ms,
        d0=step_ms,
        d1=0.0,
        prefill_per_token=lm.c1,
    )
    res = engine.serve_tokens(reqs, LengthAwareTokenScheduler(cfg), dec)
    served = report("decode/length_aware", res)
    check(served > 0, "decode/length_aware served requests")
    done = [r for r in reqs if r.tokens_done == r.out_tokens]
    check(len(done) == served, "every served token request got all its tokens")


def _close(name: str, out, want) -> None:
    out, want = np.asarray(out, np.float32), np.asarray(want, np.float32)
    err = float(np.max(np.abs(out - want) - RTOL * np.abs(want)))
    check(
        bool(np.isfinite(out).all()) and err <= ATOL,
        f"{name} matches kernels/ref.py (max |out-ref| - {RTOL}|ref| = {err!r} <= {ATOL})",
    )


def kernel_phase() -> None:
    rng = np.random.default_rng(0)
    b, h, s, hd = 8, 12, 256, 64
    q1 = rng.standard_normal((b, h, hd), np.float32)
    qs = rng.standard_normal((b, h, s, hd), np.float32)
    k = rng.standard_normal((b, h, s, hd), np.float32)
    v = rng.standard_normal((b, h, s, hd), np.float32)
    lens = jnp.asarray(rng.integers(1, s + 1, size=b), jnp.int32)
    for dtype in (jnp.float32, jnp.bfloat16):
        qd, qf, kd, vd = (jnp.asarray(x, dtype) for x in (q1, qs, k, v))
        # The references see the same (rounded) inputs, in f32 at full precision.
        q32, qf32, k32, v32 = (x.astype(jnp.float32) for x in (qd, qf, kd, vd))
        tag = jnp.dtype(dtype).name
        with jax.default_matmul_precision("highest"):
            want_d = ref.decode_attention_ref(q32, k32, v32, lens)
            want_f = ref.flash_attention_ref(qf32, k32, v32, causal=True, lengths=lens)
        check(
            "tpu_custom_call" in decode_attention.lower(qd, kd, vd, lens).as_text()
            and "tpu_custom_call" in flash_attention.lower(qf, kd, vd, lens).as_text(),
            f"decode/flash attention ({tag}) lower to compiled kernels",
        )
        _close(f"decode_attention {tag} {(b, h, s, hd)}", decode_attention(qd, kd, vd, lens), want_d)
        _close(f"flash_attention {tag} {(b, h, s, hd)}", flash_attention(qf, kd, vd, lens), want_f)


def pool_phase(engine: ServingEngine, lm, devices: list[jax.Device]) -> None:
    """One trace through four Orloj replicas, one per chip, then through
    four replicas sharing one chip."""
    n, replicas = 128, len(devices)
    bs = engine.cfg.batch_sizes

    def trace():
        # Regenerated from the seed for each pool: serving mutates requests.
        # Offered load is 0.7 of each replica's capacity.
        return engine.make_requests(
            n, lm, length_sampler=bimodal_length, utilization=0.7 * replicas, seed=1
        )

    executors = [engine.executor_for(device=d) for d in devices]
    check(
        all(x.devices() == {d} for ex, d in zip(executors, devices)
            for x in jax.tree.leaves(ex.params)),
        "each replica's params live on its own chip",
    )
    toks = jnp.asarray(np.random.default_rng(0).integers(1, 1000, (1, 128), np.int32))
    fwd = jax.jit(engine.model.logits)
    outs = [np.asarray(fwd(ex.params, {"tokens": jax.device_put(toks, d)}), np.float32)
            for ex, d in zip(executors, devices)]
    check(all(np.isfinite(o).all() for o in outs), "logits finite on every chip")
    spread = max(float(np.max(np.abs(o - outs[0]))) for o in outs)
    check(spread <= 1e-3, f"every chip's replica gives the same logits (max diff {spread!r})")

    reqs, hist = trace()
    scheds = [make_scheduler("orloj", lm, hist, bs) for _ in range(replicas)]
    for ex in executors:
        ex.drain_measured()
    multi = engine.serve_pool(reqs, scheds, executors=executors)
    report(f"pool/{replicas} replicas on {replicas} chips", multi)
    batches = [len(ex.drain_measured()) for ex in executors]
    for d, nb in zip(devices, batches):
        print(f"pool: device {d.id} ({d.device_kind}) ran {nb} batches")
    check(all(nb > 0 for nb in batches), "every chip ran batches")

    reqs, hist = trace()
    scheds = [make_scheduler("orloj", lm, hist, bs) for _ in range(replicas)]
    one = engine.serve_pool(reqs, scheds)
    report(f"pool/{replicas} replicas on 1 chip", one)
    check(
        multi.n_total == one.n_total == n,
        f"both pools account for the same {n} requests",
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    chips = ap.parse_args().chips
    devices = require_tpu(chips)
    dev = devices[0]
    print(f"device_kind: {dev.device_kind} (platform {dev.platform}, {len(devices)} chips)")
    print(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    engine, lm = build_engine()
    clock.report("engine profile")
    if chips == 1:
        prefill_phase(engine, lm)
        decode_phase(engine, lm)
        kernel_phase()
    else:
        pool_phase(engine, lm, devices)
    clock.report("all phases")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())},
    }))


if __name__ == "__main__":
    main()
