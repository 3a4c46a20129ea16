"""Find a cell's knee once: set up, derive its limits, then offer a few rates.

    python3 benchmarks/chip/sweep.py --workload gpt-bimodal --seconds 8 \\
        --rates 300,500,700,900 --derive 1

With ``--derive 1`` the cell's limits are worked out from this set-up's
measurements first and printed, as the mix files record them:

- atomic mixes: SLO = 3 x the 99th percentile of the alone time
  (c0 + c1 x bucket) over the mix's lengths, as the repository's
  ``ServingEngine.make_requests`` sets it;
- token mixes: TPOT limit = 2 x the median served decode step on the wall
  clock (one window at the first rate: the program's host work per step is
  part of what a client waits for, the jitted step alone is not);
  TTFT limit = (8 + 2 x (c0 + c1 x slots x longest prompt) / TPOT) x TPOT,
  room for one full prefill batch of joins, as ``chip_smoke.py`` sets it.

Each rate then runs one window, with fresh schedulers, on a seed of its own,
and prints one line: the rate, ``finish_rate`` and the tail, and how long
the loop ran past the window (a growing backlog drains late).  The knee is
the highest rate whose finish_rate is at least 90% with no growing backlog;
a cell is set at 0.8 x the knee.  Used once when a cell is defined; the
benchmark's runs never call it.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402

import harness  # noqa: E402
import stats  # noqa: E402
import traffic  # noqa: E402


def derive(s: harness.Setup, step_wall_ms: float = 0.0) -> dict:
    mix, lm = s.mix, s.lm
    if mix["kind"] == "tokens":
        tpot = 2.0 * step_wall_ms
        prefill = lm.c0 + lm.c1 * mix["slots"] * mix["prompt"]["hi"]
        return {"tpot_ms": tpot, "ttft_ms": (8.0 + 2.0 * prefill / tpot) * tpot}
    from repro.serving.batcher import bucket_for

    buckets = tuple(s.cfg["engine"]["buckets"])
    base = np.random.default_rng(int(mix["base_seed"]))
    lengths = np.minimum(traffic.sample_lengths(mix["prompt"], 4096, base), max(buckets))
    alone = lm.c0 + lm.c1 * np.array([bucket_for(int(n), buckets) for n in lengths], np.float64)
    return {"slo_ms": 3.0 * float(np.quantile(alone, 0.99))}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    ap.add_argument("--derive", type=int, choices=(0, 1), default=1)
    ap.add_argument("--seed", type=int, default=990001)
    a = ap.parse_args()
    bench = harness.load_benchmark()
    cell = harness.find(bench["workloads"], a.workload, "workload")
    cfg = harness.load_config(bench, cell["config"])
    mix = traffic.load_mix(harness.mix_path(cell["traffic"]))
    devices = harness.require_devices(cell["chips"])
    s, clock = harness.prepare(cfg, mix, a.seed, devices, T_START)
    rates = [float(r) for r in a.rates.split(",")]
    if a.derive:
        step_wall = 0.0
        if mix["kind"] == "tokens":
            mix["arrivals"]["rate_per_s"] = rates[0]
            w = harness.serve(s, harness.make_requests(s, a.seed, a.seconds), a.seed, a.seconds, clock)
            step_wall = float(np.median([end - start for start, end, *_ in w.execs[0].steps]))
        limits = derive(s, step_wall)
        mix.update(limits)
        print(f"derived limits: {json.dumps(limits)} (c0={s.lm.c0!r} ms, c1={s.lm.c1!r} ms/token, "
              f"jitted step={s.step_ms!r} ms)", flush=True)
    for i, rate in enumerate(rates):
        mix["arrivals"]["rate_per_s"] = rate
        seed = a.seed + 1 + i
        reqs = harness.make_requests(s, seed, a.seconds)
        w = harness.serve(s, reqs, seed, a.seconds, clock)
        res = w.result
        row = {
            "rate_per_s": rate,
            "attempted": len(reqs),
            "finish_rate": stats.finish_rate(reqs, mix.get("ttft_ms") if mix["kind"] == "tokens" else None),
            "dropped": res.n_dropped,
            "late": res.n_finished_late,
            "unserved": res.n_unserved,
            "past_window_ms": w.drained_ms - a.seconds * 1e3,
            "arrival_late_p99_ms": float(np.percentile(w.pacer.arrival_late_ms, 99)),
            "compiles": w.compiles,
            "gc_ms": sum(w.gc_ms),
            "stalls": {k: [len(v), max(v)] for k, v in w.pacer.stalls.items()},
        }
        walls = [b[2] - b[1] for ex in w.execs for b in getattr(ex, "batches", [])]
        walls += [st[1] - st[0] for ex in w.execs for st in getattr(ex, "steps", [])]
        row["call_wall_p50_ms"] = float(np.median(walls)) if walls else None
        if mix["kind"] == "tokens":
            row["ttft_p95_ms"] = stats.p95(stats.ttfts(reqs))
            row["tpot_p95_ms"] = stats.p95(stats.tpots(reqs))
        else:
            row["latency_p95_ms"] = stats.p95(stats.latencies(reqs))
        print("sweep " + json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
