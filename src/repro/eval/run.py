"""Grid runner CLI: run a conformance grid, write ``BENCH_eval.json``,
and gate on the paper's qualitative claims.

    PYTHONPATH=src python -m repro.eval.run --grid small|full|engine-smoke
        [--jobs N] [--out BENCH_eval.json] [--no-gate] [--verbose]

Exit status is 0 iff every conformance claim passed, with two exceptions:
``--no-gate`` always exits 0, and *ungated* grids (``engine-smoke``) are
tracked rather than failed — their claim verdicts and the sim-vs-engine
``engine_drift`` section are recorded in the artifact, but real-substrate
finish rates are measurements and CI-runner timing variance is not yet
characterized (DESIGN.md §8).
"""

from __future__ import annotations

import argparse
import sys
import time

from .claims import evaluate_claims, format_report
from .grid import GRIDS
from .runner import DEFAULT_ARTIFACT, run_specs, write_artifact
from .substrate import drift_report

# Grids whose claim verdicts are recorded but never fail the exit status.
UNGATED_GRIDS = frozenset({"engine-smoke"})


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--grid", default="small", choices=sorted(GRIDS))
    ap.add_argument(
        "--jobs",
        type=int,
        default=0,
        help="worker processes (0 = one per CPU, 1 = serial); engine cells "
        "always run serially in the host process",
    )
    ap.add_argument("--out", default=DEFAULT_ARTIFACT)
    ap.add_argument(
        "--no-gate",
        action="store_true",
        help="record claim verdicts in the artifact but always exit 0",
    )
    ap.add_argument(
        "--verbose", action="store_true", help="print per-cell claim evidence"
    )
    args = ap.parse_args(argv)

    specs = GRIDS[args.grid]()
    if any(s.substrate != "sim" for s in specs):
        # Imported here: sim-only grids stay JAX-free.
        from ..compile_cache import enable_compile_cache

        enable_compile_cache()
    t0 = time.time()  # simlint: ignore[R1] -- CLI progress banner, reporting only
    print(f"# grid {args.grid}: {len(specs)} cells, jobs={args.jobs or 'auto'}",
          file=sys.stderr, flush=True)
    results = run_specs(specs, jobs=args.jobs)
    claims = evaluate_claims(results)
    drift = drift_report(results)
    extra = {"engine_drift": drift} if drift else None
    write_artifact(args.out, results, grid=args.grid, claims=claims, extra=extra)
    # simlint: ignore[R1] -- CLI progress banner, reporting only
    print(f"# {len(results)} results -> {args.out} ({time.time() - t0:.1f}s)",
          file=sys.stderr)
    print(format_report(claims, verbose=args.verbose))
    if drift:
        print(
            f"engine drift: {drift['n_cells']} cells, "
            f"|finish-rate drift| mean {drift['mean_abs_finish_rate_drift']:.3f} "
            f"max {drift['max_abs_finish_rate_drift']:.3f}, "
            f"batch-time MAPE {drift['mean_batch_mape']:.3f}"
        )
    if args.no_gate:
        return 0
    if args.grid in UNGATED_GRIDS:
        print(f"# grid {args.grid!r} is tracked, not gated (DESIGN.md §8)",
              file=sys.stderr)
        return 0
    return 0 if all(c.passed for c in claims) else 1


if __name__ == "__main__":
    raise SystemExit(main())
