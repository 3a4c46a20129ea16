"""Real-execution serving engine: ORLOJ scheduling over actual JAX model
inference with measured wall-clock execution times.

This is the paper's full loop running for real on jitted models, on
whatever backend JAX has (a TPU chip, or the host CPU in the tests):
variable-length requests → Orloj (or baseline) scheduler → padded batch
(bucketed static shapes, one compiled program per bucket) → measured
execution feeds the online profiler.  Time is *hybrid*: the clock advances
by real measured execution during batches and skips idle gaps, so a trace
that spans minutes replays in seconds while every latency that matters is
genuinely measured.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.distributions import BatchLatencyModel
from ..core.eventloop import SimResult, Worker, run_event_loop, simulate
from ..core.request import Request
from ..core.scheduler import Batch
from ..models import Model, ModelConfig
from ..tracing import span
from .batcher import bucket_for, make_padded_batch, padded_batch_size
from .faults import FaultPlan
from .trace import offered_rate

__all__ = ["EngineConfig", "JaxExecutor", "DecodeJaxExecutor", "ServingEngine"]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    buckets: tuple[int, ...] = (32, 64, 128, 256)
    batch_sizes: tuple[int, ...] = (1, 2, 4, 8)
    profile_reps: int = 3
    # When > 0, a batch whose measured execution exceeds this is aborted
    # at the timeout and its requests go through the fault tier's
    # deadline-aware retry gate (DESIGN.md §11) — the real engine's
    # defense against a pathological straggler batch wedging the worker.
    batch_timeout_ms: float = 0.0


class JaxExecutor:
    """Executor for the simulator loop that runs the real model and returns
    the *measured* batch execution time (ms).

    Every served batch is appended to :attr:`measured` as ``(padded_k,
    bucket, measured_ms)`` — the executed shape plus its wall-clock — so
    callers (the real-engine eval tier) can attribute predicted-vs-measured
    drift per batch.  Profiling calls go through :meth:`_run` directly and
    are *not* logged.  The log is a bounded ring (:data:`MEASURED_LOG_CAP`
    most recent batches) so callers that never read it — long-running
    serving processes, the examples — cannot leak memory; use
    :meth:`drain_measured` to read-and-reset it around one serving run.

    With ``device`` set, the executor holds its own copy of the params on
    that device and runs every batch there (one replica per chip); without
    it, arrays go to JAX's default device.

    The forward runs on a compute-dtype copy of the weights it only uses
    rounded to that dtype (:meth:`Model.serving_params`), made once each
    time :attr:`params` is assigned and never per batch; :attr:`params`
    itself stays the master weights as assigned.  Leaves the copy does not
    cast are shared with the masters."""

    MEASURED_LOG_CAP = 4096

    def __init__(
        self, model: Model, params, cfg: EngineConfig, device: jax.Device | None = None
    ):
        self.model = model
        self.device = device
        self.cfg = cfg
        self._fwd = jax.jit(
            lambda p, batch: self.model.logits(p, batch),
        )
        self._cast = jax.jit(lambda xs: [x.astype(model.dtype) for x in xs])
        self.n_weight_casts = 0  # served copies made: one per assignment
        self.cast_bytes_saved = 0  # master bytes a forward no longer converts
        self.params = params if device is None else jax.device_put(params, device)
        self._compiled: set[tuple[int, int]] = set()
        self.measured: deque[tuple[int, int, float]] = deque(
            maxlen=self.MEASURED_LOG_CAP
        )

    @property
    def params(self):
        """The weights as assigned; the forward runs on their served copy."""
        return self._params

    @params.setter
    def params(self, params) -> None:
        self._params = params
        self._served = None  # the old copy goes before the new one is made
        if params is None:
            return
        with span("orloj.exec.cast"):
            self._served = jax.block_until_ready(self._served_copy(params))
        self.n_weight_casts += 1
        self.cast_bytes_saved = sum(
            m.nbytes
            for m, s in zip(jax.tree.leaves(params), jax.tree.leaves(self._served))
            if m.dtype != s.dtype
        )

    def _served_copy(self, params):
        """:meth:`Model.serving_params` of ``params``: the leaves it casts,
        cast in one program; every other leaf is the masters' own array,
        so masters already in the compute dtype cost no second copy."""
        leaves, tree = jax.tree.flatten(params)
        want = jax.tree.leaves(jax.eval_shape(self.model.serving_params, params))
        todo = [i for i, (m, w) in enumerate(zip(leaves, want)) if m.dtype != w.dtype]
        for i, x in zip(todo, self._cast([leaves[i] for i in todo])):
            leaves[i] = x
        return jax.tree.unflatten(tree, leaves)

    def drain_measured(self) -> list[tuple[int, int, float]]:
        """Return the ``(padded_k, bucket, measured_ms)`` log and reset it."""
        out = list(self.measured)
        self.measured.clear()
        return out

    def padded_batch_size(self, k: int) -> int:
        return padded_batch_size(k, self.cfg.batch_sizes)

    def _pad_rows(self, tokens: np.ndarray) -> np.ndarray:
        """Zero rows up to the next supported batch size."""
        k = self.padded_batch_size(tokens.shape[0])
        if k > tokens.shape[0]:
            tokens = np.concatenate(
                [tokens, np.zeros((k - tokens.shape[0],) + tokens.shape[1:], tokens.dtype)]
            )
        return tokens

    def _run(self, tokens: np.ndarray) -> tuple[float, int]:
        """Execute one padded batch; returns ``(measured_ms, padded_k)``.

        The padded batch size is what the hardware actually ran — the
        latency model must be fit against it (not the requested k), or the
        scheduler's Eq.-3 estimates diverge from measurements whenever a
        batch is padded up to the next supported size."""
        with span("orloj.exec.pad"):
            tokens = self._pad_rows(tokens)
        return self._execute(tokens)

    def _execute(self, tokens: np.ndarray) -> tuple[float, int]:
        """Run a batch already padded to a supported size (see :meth:`_run`)."""
        key = tokens.shape
        with span("orloj.exec.put"):
            batch = {"tokens": jax.device_put(tokens, self.device)}
        if key not in self._compiled:
            # warm the cache so compile time never pollutes a measurement
            with span("orloj.exec.compile"):
                jax.block_until_ready(self._fwd(self._served, batch))
            self._compiled.add(key)
        t0 = time.perf_counter()  # simlint: ignore[R1] -- this executor's whole job is measuring real batch latency
        with span("orloj.exec.dispatch"):
            out = self._fwd(self._served, batch)
        with span("orloj.exec.wait"):
            jax.block_until_ready(out)
        return (time.perf_counter() - t0) * 1e3, key[0]  # simlint: ignore[R1] -- real batch latency measurement

    def __call__(self, batch: Batch, now: float) -> float:
        # Admission (make_requests) caps lengths at the largest bucket, so
        # overflow here is a programming error — fail loudly.
        with span("orloj.exec.pad"):
            padded = make_padded_batch(batch.requests, self.cfg.buckets, overflow="error")
            tokens = self._pad_rows(padded.tokens)
        ms, k_pad = self._execute(tokens)
        self.measured.append((k_pad, padded.labels_bucket, ms))
        return ms


class DecodeJaxExecutor:
    """Measured decode-step executor for the continuous-batching loop
    (DESIGN.md §12): one token step of the running batch = one real
    flash-decode attention call over a ring-buffer KV cache, timed on the
    actual backend.

    The event loop calls :meth:`step_time` once per token step with the
    post-join active set.  The executor keeps a fixed-capacity cache
    ``(max_batch, n_kv_heads, max_cache, head_dim)`` plus per-slot
    ``valid_len``; requests map to slots on join and free them when they
    leave the active set (EOS — reconciled by ``rid`` diff, so the
    executor needs no extra callback).  Empty slots ride along with
    ``valid_len == 0`` (the kernel masks them to zero rows), which keeps
    the decode shape static — one compiled program for the whole run,
    exactly how a serving engine runs its decode kernel.

    Per step the measured cost is
    ``prefill`` (joined prompts through the *prefill executor*'s padded
    forward — the existing :class:`JaxExecutor` path) ``+ decode`` (the
    jitted write-KV-then-attend step at full capacity).

    **Honest scope** — what is and is not real here: batch shapes, cache
    occupancy, masking, and every timed operation are real; the *values*
    (query vectors, cache contents, prompt token ids) are seeded
    synthetic — this executor prices the attention decode step, it does
    not generate text, and it deliberately omits the MLP/sampling cost
    of a full model step.  ``use_pallas=None`` picks the compiled Pallas
    kernel on a TPU backend and the jnp reference oracle elsewhere (off
    the TPU the kernel would only run in the very slow Pallas
    interpreter) — the same numerics, timed on what the backend actually
    runs.  Prompts longer than the largest
    prefill bucket are served but their cache entry is truncated to
    ``max_cache`` (a ring buffer keeps the most recent positions)."""

    def __init__(
        self,
        model_cfg: ModelConfig,
        *,
        max_batch: int = 8,
        max_cache: int = 256,
        prefill: JaxExecutor | None = None,
        use_pallas: bool | None = None,
        block_k: int = 256,
        seed: int = 0,
    ):
        if max_batch <= 0 or max_cache <= 0:
            raise ValueError(
                f"max_batch and max_cache must be positive, got "
                f"{max_batch} and {max_cache}"
            )
        self.max_batch = max_batch
        self.max_cache = max_cache
        self.n_heads = model_cfg.n_heads
        self.n_kv = model_cfg.n_kv_heads
        self.head_dim = model_cfg.head_dim or model_cfg.d_model // model_cfg.n_heads
        self.prefill = prefill
        self.use_pallas = (
            jax.default_backend() == "tpu" if use_pallas is None else use_pallas
        )
        self.block_k = block_k
        self._rng = np.random.default_rng(seed)
        self._slot: dict[int, int] = {}  # rid -> cache slot
        self._free = list(range(max_batch - 1, -1, -1))
        shape = (max_batch, self.n_kv, max_cache, self.head_dim)
        self._kc = jnp.zeros(shape, jnp.float32)
        self._vc = jnp.zeros(shape, jnp.float32)
        self._valid = jnp.zeros((max_batch,), jnp.int32)
        self._step = jax.jit(
            self._step_impl, static_argnames=("use_pallas", "block_k")
        )
        # Warm the compile cache so the first measured step is not a
        # compile (mirrors JaxExecutor._run's warm-up discipline).
        self._decode_once()

    # ------------------------------------------------------------ internals
    @staticmethod
    def _step_impl(kc, vc, valid, active, q, nk, nv, *, use_pallas, block_k):
        """Write this step's K/V at each active slot's ring position,
        advance ``valid_len``, attend.  Inactive slots pass through
        untouched and attend over zero valid positions."""
        from ..kernels.ops import decode_attention

        s = kc.shape[2]
        pos = valid % s

        def write(cache, new):
            return jax.vmap(
                lambda c, n, p: jax.lax.dynamic_update_slice(
                    c, n[:, None, :], (0, p, 0)
                )
            )(cache, new, pos)

        sel = active[:, None, None, None]
        kc2 = jnp.where(sel, write(kc, nk), kc)
        vc2 = jnp.where(sel, write(vc, nv), vc)
        valid2 = jnp.where(active, jnp.minimum(valid + 1, s), valid)
        out = decode_attention(
            q, kc2, vc2, valid2, use_pallas=use_pallas, block_k=block_k
        )
        return kc2, vc2, valid2, out

    def lower_step(self) -> jax.stages.Lowered:
        """The decode step lowered at this executor's shapes, for inspecting
        the program (a compiled Pallas kernel shows as ``tpu_custom_call``)."""
        b, h, kv, hd = self.max_batch, self.n_heads, self.n_kv, self.head_dim
        return self._step.lower(
            self._kc, self._vc, self._valid, self._valid > 0,
            jnp.zeros((b, h, hd), jnp.float32),
            jnp.zeros((b, kv, hd), jnp.float32),
            jnp.zeros((b, kv, hd), jnp.float32),
            use_pallas=self.use_pallas, block_k=self.block_k,
        )

    def _decode_once(self) -> float:
        """One measured decode step at full capacity (ms); mutates the
        cache state of the active slots."""
        b, h, hd = self.max_batch, self.n_heads, self.head_dim
        # Synthetic values are drawn OUTSIDE the timed region: the
        # measurement prices the kernel step, not host-side rng.
        with span("orloj.decode.values"):
            q = jnp.asarray(self._rng.standard_normal((b, h, hd)), jnp.float32)
            nk = jnp.asarray(
                self._rng.standard_normal((b, self.n_kv, hd)), jnp.float32
            )
            nv = jnp.asarray(
                self._rng.standard_normal((b, self.n_kv, hd)), jnp.float32
            )
            active = self._valid > 0  # slots currently holding a request
        t0 = time.perf_counter()  # simlint: ignore[R1] -- real decode-step latency measurement
        with span("orloj.decode.dispatch"):
            kc, vc, valid, out = self._step(
                self._kc, self._vc, self._valid, active, q, nk, nv,
                use_pallas=self.use_pallas, block_k=self.block_k,
            )
        with span("orloj.decode.wait"):
            jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) * 1e3  # simlint: ignore[R1] -- real decode-step latency measurement
        self._kc, self._vc, self._valid = kc, vc, valid
        # (B, H, hd) attention output of the last step — synthetic-valued,
        # kept for kernel-integration tests and debugging.
        self.last_out = out
        return ms

    def _prefill_ms(self, joined: Sequence[Request]) -> float:
        """Price the joined prompts through the padded prefill forward and
        seed their cache slots.  Without a prefill executor the forward is
        skipped (decode-only pricing) but slots are still seeded."""
        ms = 0.0
        lens = [max(int(r.prompt_tokens), 1) for r in joined]
        if self.prefill is not None:
            bucket = bucket_for(
                min(max(lens), max(self.prefill.cfg.buckets)),
                self.prefill.cfg.buckets,
            )
            toks = np.zeros((len(joined), bucket), np.int32)
            for i, l in enumerate(lens):
                n_tok = min(l, bucket)
                toks[i, :n_tok] = self._rng.integers(1, 1000, size=n_tok)
            ms, _ = self.prefill._run(toks)
        with span("orloj.decode.seed"):
            for r, l in zip(joined, lens):
                if not self._free:
                    raise RuntimeError(
                        f"decode executor capacity exceeded: {len(self._slot)} "
                        f"active slots of {self.max_batch}; the token scheduler "
                        f"must admit at most max_batch concurrent requests"
                    )
                slot = self._free.pop()
                self._slot[r.rid] = slot
                n_ctx = min(l, self.max_cache)
                kv = self._rng.standard_normal(
                    (2, self.n_kv, n_ctx, self.head_dim)
                ).astype(np.float32)
                self._kc = self._kc.at[slot, :, :n_ctx, :].set(kv[0])
                self._vc = self._vc.at[slot, :, :n_ctx, :].set(kv[1])
                self._valid = self._valid.at[slot].set(n_ctx)
        return ms

    def _release_departed(self, active: Sequence[Request]) -> None:
        with span("orloj.decode.release"):
            live = {r.rid for r in active}
            for rid in [r for r in self._slot if r not in live]:
                slot = self._slot.pop(rid)
                self._valid = self._valid.at[slot].set(0)
                self._free.append(slot)

    # ------------------------------------------------------------- API
    def calibrate(self, reps: int = 3) -> float:
        """Median measured decode-step ms at *full* batch capacity — the
        request-generation rate anchor (cache state is restored)."""
        kc, vc, valid = self._kc, self._vc, self._valid
        self._valid = jnp.full((self.max_batch,), self.max_cache, jnp.int32)
        ts = [self._decode_once() for _ in range(reps)]
        self._kc, self._vc, self._valid = kc, vc, valid
        return float(np.median(ts))

    def step_time(
        self, active: Sequence[Request], joined: Sequence[Request], now: float
    ) -> float:
        """Measured ms for one token step: joined prompts' prefill plus
        the full-capacity decode attention step."""
        if not active:
            raise ValueError("step_time called with an empty active set")
        # Departures first (frees slots), then joins (claims them).
        self._release_departed(active)
        ms = self._prefill_ms(joined) if joined else 0.0
        return ms + self._decode_once()


@dataclasses.dataclass
class _ScaledExecutor:
    """A replica whose hardware is ``scale``× slower than the measured
    backend: the shared executor runs the batch for real, and the measured
    duration is scaled before it reaches the virtual clock.  This is how a
    heterogeneous pool is modelled on one physical backend — accounting is
    still anchored to a real measurement per batch."""

    inner: JaxExecutor
    scale: float

    def __call__(self, batch: Batch, now: float) -> float:
        return self.scale * self.inner(batch, now)


class ServingEngine:
    """Profiles the model's Eq.-3 latency curve, generates length-driven
    requests, and runs any scheduler against real execution.

    **Determinism contract** (the seed hooks the eval tier relies on):
    everything *upstream* of execution is seeded — model parameters from
    ``seed`` (:attr:`seed` records it), request generation from the
    ``seed`` passed to :meth:`make_requests`, zero-padding in the batcher —
    so two engines built with the same config and seed serve byte-identical
    batches.  The measured durations themselves are real wall-clock and
    therefore machine- and run-dependent; that is the point of the engine
    substrate, and downstream consumers must not treat them as stable."""

    def __init__(self, model_cfg: ModelConfig, cfg: EngineConfig | None = None, seed: int = 0):
        self.cfg = cfg or EngineConfig()
        self.seed = seed
        self.model = Model(model_cfg)
        self.params = self.model.init(jax.random.PRNGKey(seed))
        self.executor = JaxExecutor(self.model, self.params, self.cfg)
        self._device_executors: dict[jax.Device, JaxExecutor] = {}

    def executor_for(
        self, scale: float = 1.0, device: jax.Device | None = None
    ) -> JaxExecutor | _ScaledExecutor:
        """Executor factory for pool construction: ``scale == 1`` returns
        the shared measured executor; ``scale > 1`` wraps it so the replica
        appears ``scale``× slower (heterogeneous pools, one real backend).
        With ``device`` set, the executor is this engine's replica on that
        device (built once per device, with its own copy of the params)."""
        if scale <= 0.0:
            raise ValueError(f"executor scale must be positive, got {scale}")
        ex = self.executor
        if device is not None:
            if device not in self._device_executors:
                self._device_executors[device] = JaxExecutor(
                    self.model, self.params, self.cfg, device
                )
            ex = self._device_executors[device]
        return ex if scale == 1.0 else _ScaledExecutor(ex, scale)

    # -------------------------------------------------------- profiling
    def profile_latency_model(self) -> BatchLatencyModel:
        """Fit Eq. 3 (l_B = c0 + c1·k·l) from measured (k, bucket) grid.

        On an XLA backend the 'size' l is the padded bucket length in
        tokens; c1 converts tokens→ms."""
        # The grid over supported batch sizes is complete: any off-grid
        # batch pads up to a supported size before executing, so it would
        # measure an identical shape.  Fitting against the executed size
        # reported by _run keeps the attribution correct by construction
        # (requested k and executed k coincide exactly on this grid).
        xs, ys = [], []
        for bucket in self.cfg.buckets:
            for k in sorted(set(self.cfg.batch_sizes)):
                toks = np.ones((k, bucket), np.int32)
                ts, k_pad = [], k
                for _ in range(self.cfg.profile_reps):
                    ms, k_pad = self.executor._run(toks)
                    ts.append(ms)
                xs.append((k_pad, bucket))
                ys.append(float(np.median(ts)))
        a = np.array([[1.0, k * l] for k, l in xs])
        coef, *_ = np.linalg.lstsq(a, np.array(ys), rcond=None)
        c0, c1 = float(max(coef[0], 0.01)), float(max(coef[1], 1e-6))
        return BatchLatencyModel(c0=c0, c1=c1, bucket=0.0)

    # ------------------------------------------------------ request gen
    def make_requests(
        self,
        n: int,
        lm: BatchLatencyModel,
        *,
        length_sampler: Callable[[np.random.Generator], int],
        slo_scale: float = 3.0,
        utilization: float = 0.7,
        seed: int = 0,
    ) -> tuple[list[Request], dict]:
        """Length-driven requests: the execution-time 'distribution' is the
        real consequence of the token-length distribution (the paper's NLP
        case).  true_time is the request's intrinsic size in c1-units
        (= padded token count), so Eq. 3 reproduces measured latency."""
        from .batcher import bucket_for

        rng = np.random.default_rng(seed)
        lengths = np.array([length_sampler(rng) for _ in range(n)])
        # Admission control: the serving path cannot represent payloads
        # beyond the largest bucket, so cap lengths here (explicitly, once)
        # rather than letting the batcher truncate tokens silently.
        lengths = np.minimum(lengths, max(self.cfg.buckets))
        sizes = np.array(
            [bucket_for(int(l), self.cfg.buckets) for l in lengths], np.float64
        )
        alone = lm.c0 + lm.c1 * sizes
        p99 = float(np.quantile(alone, 0.99))
        slo = slo_scale * p99

        rate = offered_rate(
            sizes, lm, utilization, self.cfg.batch_sizes[-1], rng
        )
        gaps = rng.exponential(1.0 / rate, size=n)
        arrivals = np.cumsum(gaps)

        reqs = []
        for i in range(n):
            tok = rng.integers(1, 1000, size=int(lengths[i])).astype(np.int32)
            reqs.append(
                Request(
                    app_id="short" if lengths[i] <= np.median(lengths) else "long",
                    release=float(arrivals[i]),
                    slo=slo,
                    true_time=float(sizes[i]),
                    payload=tok,
                )
            )
        hist = {
            "short": sizes[lengths <= np.median(lengths)],
            "long": sizes[lengths > np.median(lengths)],
        }
        return reqs, hist

    def decode_executor(
        self,
        *,
        max_batch: int = 8,
        max_cache: int = 256,
        use_pallas: bool | None = None,
        seed: int | None = None,
    ) -> DecodeJaxExecutor:
        """Build a :class:`DecodeJaxExecutor` over this engine's model
        dims, wired to the shared measured prefill executor."""
        return DecodeJaxExecutor(
            self.model.cfg,
            max_batch=max_batch,
            max_cache=max_cache,
            prefill=self.executor,
            use_pallas=use_pallas,
            seed=self.seed if seed is None else seed,
        )

    def make_token_requests(
        self,
        n: int,
        decode: DecodeJaxExecutor,
        *,
        mean_out: float = 24.0,
        tpot_scale: float = 2.0,
        ttft_mult: float = 8.0,
        utilization: float = 0.7,
        prompt_lo: int = 16,
        prompt_hi: int = 128,
        seed: int = 0,
    ) -> list[Request]:
        """Token-mode requests anchored to the *measured* decode step:
        geometric output lengths (mean ``mean_out``), uniform prompts,
        TPOT SLO = ``tpot_scale`` × the calibrated full-batch step time,
        TTFT = ``ttft_mult`` × TPOT, arrival rate offering
        ``utilization`` of a worker continuously batching at capacity —
        the engine-substrate analogue of
        :func:`repro.serving.trace.generate_token_requests`."""
        step_ms = decode.calibrate()
        tpot = tpot_scale * step_ms
        ttft = ttft_mult * tpot
        rng = np.random.default_rng(seed)
        out = np.maximum(rng.geometric(1.0 / mean_out, size=n), 1)
        prompts = rng.integers(prompt_lo, prompt_hi + 1, size=n)
        rate = utilization * decode.max_batch / (step_ms * mean_out)
        arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n))
        return [
            Request(
                app_id="tok",
                release=float(t),
                slo=ttft + tpot * (float(o) - 1.0),
                true_time=float(o) * step_ms,
                prompt_tokens=int(p),
                out_tokens=int(o),
            )
            for t, o, p in zip(arrivals, out, prompts)
        ]

    # ------------------------------------------------------------- run
    def serve_tokens(
        self,
        requests: Sequence[Request],
        scheduler,
        decode: DecodeJaxExecutor,
        *,
        engine: str = "scalar",
    ) -> SimResult:
        """Serve a token-mode request set through the continuous-batching
        loop with measured decode steps (DESIGN.md §12).  The scheduler
        must be a token scheduler (``repro.core.tokensched``) whose
        ``max_batch`` does not exceed the executor's slot capacity."""
        cap = getattr(getattr(scheduler, "cfg", None), "max_batch", None)
        if cap is not None and cap > decode.max_batch:
            raise ValueError(
                f"scheduler admits up to {cap} concurrent requests but the "
                f"decode executor has only {decode.max_batch} cache slots"
            )
        return run_event_loop(
            list(requests), [Worker(scheduler, decode)], engine=engine
        )

    def serve(self, requests: Sequence[Request], scheduler) -> SimResult:
        faults = None
        if self.cfg.batch_timeout_ms > 0.0:
            faults = FaultPlan(batch_timeout_ms=self.cfg.batch_timeout_ms)
        return simulate(list(requests), scheduler, self.executor, faults=faults)

    def serve_pool(
        self,
        requests: Sequence[Request],
        schedulers: Sequence,
        policy: str = "least_loaded",
        seed: int = 0,
        horizon: float | None = None,
        charge_scheduler_overhead: bool = False,
        executors: Sequence | None = None,
    ) -> SimResult:
        """Serve one arrival stream across N replica schedulers (§3.1).

        By default all replicas share this engine's measured JAX executor
        (one physical backend timed once per batch); pass ``executors``
        (one per scheduler, e.g. from :meth:`executor_for`) to build a
        heterogeneous pool of fast and scaled-slow replicas, or a pool
        with one replica per device.  The front-end
        ``policy`` assigns arrivals to replicas."""
        if executors is None:
            executors = [self.executor] * len(schedulers)
        if len(executors) != len(schedulers):
            raise ValueError(
                f"got {len(schedulers)} schedulers but {len(executors)} executors"
            )
        return run_event_loop(
            list(requests),
            [Worker(s, e) for s, e in zip(schedulers, executors)],
            policy=policy,
            seed=seed,
            horizon=horizon,
            charge_scheduler_overhead=charge_scheduler_overhead,
        )
