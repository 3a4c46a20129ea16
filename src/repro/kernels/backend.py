"""Where a Pallas kernel runs: compiled on a TPU backend, interpreted elsewhere."""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve a kernel's ``interpret`` flag when the kernel is traced.

    ``None`` picks the compiled kernel on a TPU backend and the Pallas
    interpreter on any other backend (the CPU tests).  The backend is asked
    here, at trace time, so that importing :mod:`repro.kernels` initialises
    no backend."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
