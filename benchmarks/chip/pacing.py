"""Run the program's event loop on the client's clock.

The program's loop (``core/eventloop.py``) keeps a hybrid virtual clock: it
jumps over idle gaps and advances by whatever an executor returns.  The
adapters here wrap the program's own scheduler and executor objects so that
the same loop runs in real time without any change to it:

- no scheduler hook runs before the wall time of the event that called it
  (every event the loop handles calls one), so an arrival is never seen
  before it is due;
- an executor returns wall-clock completion minus the batch's virtual start,
  so every completion is stamped at its wall time and the host work between
  device calls (padding, ``device_put``, slot updates, the loop itself) is
  billed to the requests that waited for it.

Virtual time ``t`` (ms) maps to wall time ``t0 + t / 1000`` (s).  The loop
must run with ``charge_scheduler_overhead=False``: scheduler time already
passes on the wall clock.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Sequence

import numpy as np

STALL_MS = 10.0  # a host call this long holds every request behind it


class Pacer:
    """The shared clock: virtual ms since ``start()`` against wall time."""

    def __init__(self, annotate: Callable[[str], Any] | None = None) -> None:
        self.t0: float | None = None
        # with --trace 1: jax.profiler.TraceAnnotation, one host span per call
        self._annotate = annotate
        self.sched_s = 0.0  # wall seconds inside the wrapped scheduler hooks
        self.arrival_late_ms: list[float] = []
        self.stalls: dict[str, list[float]] = {}  # call name -> wall ms of calls over STALL_MS

    def start(self) -> None:
        self.t0 = time.perf_counter()

    def now_ms(self) -> float:
        return (time.perf_counter() - self.t0) * 1e3

    def wait(self, virtual_ms: float) -> None:
        """Block until the wall clock reaches ``virtual_ms``."""
        ahead = self.t0 + virtual_ms / 1e3 - time.perf_counter()
        if ahead > 0:
            with self.span("bench.pace_wait"):
                time.sleep(ahead)

    def timed(self, name: str, fn: Callable[[], Any]) -> tuple[Any, float]:
        """Run ``fn`` inside a host span; returns (result, wall seconds) and
        records the call among the stalls when it took over STALL_MS."""
        with self.span(name):
            t = time.perf_counter()
            out = fn()
            dt = time.perf_counter() - t
        if dt * 1e3 > STALL_MS:
            self.stalls.setdefault(name, []).append(dt * 1e3)
        return out, dt

    def span(self, name: str):
        return self._annotate(name) if self._annotate else contextlib.nullcontext()


class PacedScheduler:
    """The program's scheduler, each hook held until its event is due.

    Hook time is measured here, without the pacing wait; the loop's own
    ``SimResult.sched_time_ms`` includes the wait and is not used."""

    def __init__(self, inner: Any, pacer: Pacer) -> None:
        self.inner = inner
        self.pacer = pacer

    def __getattr__(self, name: str) -> Any:
        return getattr(self.inner, name)

    def _call(self, now: float, hook: Callable[[], Any], name: str):
        self.pacer.wait(now)
        out, dt = self.pacer.timed(name, hook)
        self.pacer.sched_s += dt
        return out

    def _arrived(self, reqs: Sequence[Any], now: float) -> None:
        wall = self.pacer.now_ms()
        self.pacer.arrival_late_ms.extend(wall - r.release for r in reqs)

    def on_arrival(self, req, now: float) -> None:
        self._call(now, lambda: self.inner.on_arrival(req, now), "bench.sched.on_arrival")
        self._arrived((req,), now)

    def on_arrivals(self, reqs, now: float) -> None:
        bulk = getattr(self.inner, "on_arrivals", None)
        if bulk is None:  # the loop's own fallback for schedulers without it
            self._call(now, lambda: [self.inner.on_arrival(r, now) for r in reqs], "bench.sched.on_arrivals")
        else:
            self._call(now, lambda: bulk(reqs, now), "bench.sched.on_arrivals")
        self._arrived(reqs, now)

    def next_batch(self, now: float):
        return self._call(now, lambda: self.inner.next_batch(now), "bench.sched.next_batch")

    def on_batch_done(self, batch, now: float, alone_times_ms) -> None:
        self._call(now, lambda: self.inner.on_batch_done(batch, now, alone_times_ms), "bench.sched.on_batch_done")

    def on_decode_step(self, finished, n_active: int, now: float):
        return self._call(now, lambda: self.inner.on_decode_step(finished, n_active, now), "bench.sched.on_decode_step")


class PacedExecutor:
    """The program's ``JaxExecutor`` for atomic batches, on the wall clock.

    Per batch it records the virtual start, the wall completion, the ms the
    program measured itself, the executed shape and the real tokens."""

    def __init__(self, inner: Any, pacer: Pacer, replica: int = 0) -> None:
        self.inner = inner
        self.pacer = pacer
        self.replica = replica
        self.batches: list[tuple] = []  # (replica, start, end, inner_ms, k_pad, bucket, lengths)
        self.current = None  # the batch being executed, for Capture

    def __call__(self, batch, now: float) -> float:
        self.pacer.wait(now)
        self.current = batch
        inner_ms, _ = self.pacer.timed("bench.execute", lambda: self.inner(batch, now))
        self.current = None
        end = self.pacer.now_ms()
        k_pad, bucket, _ = self.inner.measured[-1]
        lengths = tuple(len(r.payload) for r in batch.requests)
        self.batches.append((self.replica, now, end, inner_ms, k_pad, bucket, lengths))
        return end - now


class PacedDecodeExecutor:
    """The program's ``DecodeJaxExecutor`` on the wall clock.

    Per step it records the virtual start, the wall completion, the ms the
    program measured itself (prefill of joins plus the decode step), and the
    cache positions each active request attends over after the step."""

    def __init__(self, inner: Any, pacer: Pacer) -> None:
        self.inner = inner
        self.pacer = pacer
        self.steps: list[tuple] = []  # (start, end, inner_ms, n_joined, valid_after)
        self.current = None  # (step ordinal, valid_after) of the running step, for Capture

    def __getattr__(self, name: str) -> Any:
        return getattr(self.inner, name)

    def step_time(self, active, joined, now: float) -> float:
        self.pacer.wait(now)
        s = self.inner.max_cache
        valid = tuple(
            min(min(max(int(r.prompt_tokens), 1), s) + r.tokens_done + 1, s) for r in active
        )
        self.current = (len(self.steps), valid)
        inner_ms, _ = self.pacer.timed("bench.execute", lambda: self.inner.step_time(active, joined, now))
        self.current = None
        end = self.pacer.now_ms()
        self.steps.append((now, end, inner_ms, len(joined), valid))
        return end - now


class Capture:
    """Wraps one of the program's jitted callables and keeps what chosen
    calls returned, with their arguments, for the check after the window.

    ``keep`` decides per call from the executor-level context set by the
    harness; kept arrays stay on the device until the window has closed."""

    def __init__(self, fn: Callable, keep: Callable[[], Any]) -> None:
        self.fn = fn
        self.keep = keep
        self.kept: list[tuple[Any, tuple, Any]] = []  # (tag, args, out)

    def __call__(self, *args, **kwargs):
        out = self.fn(*args, **kwargs)
        tag = self.keep()
        if tag is not None:
            self.kept.append((tag, args, out))
        return out


def percentile(xs: Sequence[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) else float("nan")
