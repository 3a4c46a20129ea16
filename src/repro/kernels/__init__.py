"""Pallas TPU kernels for the serving data plane's compute hot spots.

Each kernel ships three artifacts:
- ``<name>.py`` — the ``pl.pallas_call`` kernel with explicit BlockSpec
  VMEM tiling, compiled on a TPU backend and run in the Pallas interpreter
  elsewhere (the CPU tests; ``tests/test_tpu_compile.py`` compiles them
  for a described v5e chip);
- ``ref.py``    — pure-jnp oracles;
- ``ops.py``    — jit'd public wrappers with a ``use_pallas`` switch.

Kernel-level tie-in to the paper: ``flash_attention`` takes *per-request
lengths* for a padded batch — the exact execution model ORLOJ schedules
around (Eq. 4: the batch runs at the padded max; masking keeps short
requests correct while the straggler determines the latency).
"""

from .ops import (
    decode_attention,
    flash_attention,
    moe_gating,
    rmsnorm,
)

__all__ = ["flash_attention", "decode_attention", "rmsnorm", "moe_gating"]
