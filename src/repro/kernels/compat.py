"""The one place that names JAX APIs whose spelling has moved between releases.

Targets the installed JAX (0.9.x).  Kernels, collectives and vecsim import
these names from here instead of reaching into ``jax``/``pltpu`` directly, so
a future rename is a one-line fix in this file:

- :data:`CompilerParams` — ``pltpu.CompilerParams`` for every Pallas TPU call.
- :func:`shard_map` — ``jax.shard_map``.
- :func:`axis_size` — ``jax.lax.axis_size`` inside ``shard_map``.
- :func:`enable_x64` — the float64 context (``jax.enable_x64``) that the
  vecsim exactness contracts with the event simulator run under.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as _pltpu

CompilerParams = _pltpu.CompilerParams
shard_map = jax.shard_map
axis_size = jax.lax.axis_size


def enable_x64():
    """Context manager that turns on 64-bit types for the enclosed trace."""
    return jax.enable_x64(True)


__all__ = ["CompilerParams", "axis_size", "enable_x64", "shard_map"]
