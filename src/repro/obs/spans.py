"""Host spans on the profiler's clock.

``span(name)`` is ``jax.profiler.TraceAnnotation(name)``: a span that a
``jax.profiler`` trace records on the host line beside the device's
operations, and that costs next to nothing when no trace runs.  Where jax
is not installed it is a null context, so that ``repro.data`` and
``repro.balance`` stay importable without it.
"""
from __future__ import annotations

import contextlib


def span(name: str):
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return contextlib.nullcontext()
    return TraceAnnotation(name)
