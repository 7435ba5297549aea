"""Host spans around vecsim's layers, on the profiler's clock.

``span(name)`` is a ``jax.profiler.TraceAnnotation`` named
``vecsim.<name>``: under an active profiler it lands on the host plane of
the same trace as the device's program runs, so host work and device idle
time can be set side by side; without one it costs about a microsecond.
Spans sit at layer boundaries only (tables, dispatch, sync, order keys,
gather, ...), never per round, deployment or request.
"""
from __future__ import annotations

PREFIX = "vecsim."


def span(name: str):
    """Context manager recording ``vecsim.<name>`` on the host plane."""
    import jax
    return jax.profiler.TraceAnnotation(PREFIX + name)
