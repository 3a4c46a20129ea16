"""The benchmark's reader of ``hybrid_prefill_mfu_pct`` and the FLOP count
it divides (``benchmarks/chip/metrics/hybrid_prefill_mfu_pct.py``,
``benchmarks/chip/shapes_hybrid.py``, loaded by path): it gives nothing or
a finite number, never anything a strict JSON line refuses."""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1] / "benchmarks/chip"
TINY = dict(
    d_model=4, d_ff=8, vocab_size=10, n_heads=2, n_kv_heads=1, mamba_heads=2, mamba_head_dim=2,
    ssm_state=3, mamba_conv=2, mamba_chunk=2, layer_pattern="MA", n_layers=2,
)
PEAKS = {"bf16_flops": 197e12}


def _load(name: str, path: Path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # the reader imports ``shapes_hybrid`` by name
    spec.loader.exec_module(mod)
    return mod


SHAPES = _load("shapes_hybrid", BENCH / "shapes_hybrid.py")
READER = _load("bench_hybrid_prefill_mfu_pct", BENCH / "metrics/hybrid_prefill_mfu_pct.py")


class _Trace:
    def __init__(self, modules_ns: dict):
        self.modules_ns = modules_ns

    def module_ns(self, match) -> int:
        return sum(v for k, v in self.modules_ns.items() if match(k))


def _run(trace=True, peaks=PEAKS, module_ns=2_000_000, lengths=((3, 2), (1,)), kind="atomic"):
    batches = [(0, 0.0, 1.0, 0.5, len(ls), 4, list(ls)) for ls in lengths]
    tv = _Trace({"jit__lambda.7": module_ns, "jit_gap": 10**9}) if trace else None
    return SimpleNamespace(trace=tv, peaks=peaks, kind=kind, model=TINY, batches=batches)


def test_flops_match_a_hand_count():
    # L = 3 on TINY: the Mamba-2 layer is in_proj 2*3*4*16 = 384, conv
    # 2*3*2*10 = 120, SSD over chunks of 2 and 1: (2*3*3 + 2*4*3 + 2*2*4*3)
    # + (2*3*1 + 2*4*1 + 2*1*4*3 + 2*1*4*3) = 90 + 62, out_proj 2*3*4*4 = 96;
    # the attention layer 2*3*4*4*2 + 2*3*2*2*4 + 2*2*2*2*6 = 384; two
    # SwiGLU MLPs 2 * 3*2*3*4*8 = 1152; the head 2*4*10 = 80.
    assert SHAPES.prefill_flops(TINY, 3) == 384 + 120 + 90 + 62 + 96 + 384 + 1152 + 80
    # one position: one chunk, nothing passed in
    assert SHAPES.prefill_flops(TINY, 1) == (
        2 * 4 * 16 + 2 * 2 * 10 + (2 * 3 + 2 * 4 + 2 * 4 * 3) + 2 * 4 * 4
        + 2 * 4 * 4 * 2 + 2 * 2 * 2 * 4 + 2 * 2 * 2 * 2 + 2 * 3 * 2 * 4 * 8 + 80
    )


def test_a_normal_run_reads_a_finite_share_of_the_peak():
    value = READER.read(_run())
    flops = sum(SHAPES.prefill_flops(TINY, n) for n in (3, 2, 1))
    assert value == pytest.approx(100.0 * flops / (2e-3 * PEAKS["bf16_flops"]))
    json.dumps({"value": value}, allow_nan=False)  # a strict line takes it


@pytest.mark.parametrize(
    "kw",
    [dict(trace=False), dict(peaks={}), dict(module_ns=0), dict(lengths=()), dict(kind="tokens")],
    ids=["no-trace", "no-peaks", "zero-module-time", "no-batches", "token-mix"],
)
def test_nothing_to_read_gives_none(kw):
    assert READER.read(_run(**kw)) is None
