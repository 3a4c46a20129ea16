"""Spans in the JAX profiler's own trace, named ``orloj.<layer>.<what>``.

``span(name)`` is a ``jax.profiler.TraceAnnotation`` (a TraceMe) once JAX
has been imported, and a shared do-nothing context before that: the
simulator in :mod:`repro.core` runs without JAX and must not import it.
A span records only while a JAX profiler trace is active, on the same clock
as the device planes; otherwise it costs one inactive TraceMe.  Nothing
here reads a clock or keeps what it records.
"""

from __future__ import annotations

import sys
from contextlib import AbstractContextManager, nullcontext

__all__ = ["span"]

_NULL = nullcontext()
_annotation = None  # jax.profiler.TraceAnnotation, once jax is imported


def span(name: str) -> AbstractContextManager:
    global _annotation
    if _annotation is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return _NULL
        _annotation = jax.profiler.TraceAnnotation
    return _annotation(name)
