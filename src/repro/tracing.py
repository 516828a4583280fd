"""Host spans of the program, on the profiler's clock.

``span(name, **ids)`` marks a stretch of host work with
``jax.profiler.TraceAnnotation``; the span is written only while a profiler
session is active (``jax.profiler.trace`` / ``start_trace``) and costs
about a microsecond otherwise, so it is always on.  Every program span is
named ``repro.<layer>.<step>``, which keeps it apart from spans a caller
records around the program.  Device-side names use ``jax.named_scope``
directly: a scope changes only the HLO metadata (each operation's
``op_name``), never the executed program or its results.
"""

from __future__ import annotations

import functools

import jax

__all__ = ["PREFIX", "span", "spanned"]

PREFIX = "repro."


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A host span ``repro.<name>`` carrying ``ids`` as its arguments
    (use as a context manager)."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **ids)


def spanned(name: str):
    """Decorator form of :func:`span`: every call of the decorated
    function runs inside the span ``repro.<name>``."""
    return functools.partial(jax.profiler.annotate_function,
                             name=PREFIX + name)
