"""The benchmark of Orloj serving on a TPU: one run of one cell.

    python3 benchmarks/chip/run.py --workload gpt-bimodal --seed 1234567890 \\
        --seconds 20 --trace 0

Run from the root of a checkout on a machine that holds the chips the cell
asks for.  Prints set-up, window and check details on earlier lines, the
numbers compared with their limits last on standard error, and one JSON
object as the last line of standard output.  Exits non-zero, printing no
result, when JAX finds no TPU or fewer chips than the cell needs.
``--control 1`` puts the lower-precision reference in the program's place
in the check (used to set the limits; the benchmark's own runs never do).
"""

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    a = ap.parse_args()
    if a.seed < 0:
        ap.error("--seed must be non-negative")
    out = harness.run_cell(a.workload, a.seed, a.seconds, bool(a.trace), T_START, bool(a.control))
    for name, v in out["checks"].items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})", file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
