"""Published peaks of each accelerator the benchmark may run on, keyed by
JAX's ``device_kind``.  A kind that is not here is an error, not a default.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" (system
architecture page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at
819 GB/s per chip.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: {sorted(PEAKS)}"
        ) from None
