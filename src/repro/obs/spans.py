"""Host spans in the profiler's own trace.

``span(name, **args)`` opens a ``jax.profiler.TraceAnnotation``: while a
profiler trace runs (``jax.profiler.start_trace`` / ``trace``) the span and
its args land on the host plane of the ``.xplane.pb``, on the same clock as
the device's operations.  With no trace running it records nothing and costs
about a microsecond.  ``Server`` opens its ``server.*`` spans through it;
``docs/observability.md`` lists them.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **args) -> TraceAnnotation:
    """A context manager: the host span ``name``, carrying ``args``."""
    return TraceAnnotation(name, **args)
