"""The chip: presence, compile cache, compile clock and peak memory."""
from __future__ import annotations

import os
import threading

from .spec import ROOT


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def require_chips(need: int):
    """The devices of a TPU host with at least ``need`` chips; never the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform}")
    if len(devs) < need:
        raise NoChip(f"the cell needs {need} chips, JAX sees {len(devs)}")
    return devs


def set_compile_cache() -> str:
    """Keep JAX's persistent compilation cache at a fixed path.

    ``$JAX_COMPILATION_CACHE_DIR`` where it is set, else ``<checkout>/.jax_cache``
    (a path that moves never hits).  Every program is cached, however quick
    its compile, so that a second run of a cell compiles nothing.
    """
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


class CompileClock:
    """Sums JAX's compile-event durations (trace, lowering, backend compile
    or cache load) and counts backend compiles and persistent-cache misses,
    so that set-up and the window can each say what they compiled."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.backend = 0        # backend compiles, cache loads included
        self.misses = 0         # persistent-cache misses: real compiles
        self._lock = threading.Lock()

    def install(self) -> "CompileClock":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event, duration, **kw):
        if event not in self.EVENTS:
            return
        with self._lock:
            self.seconds += duration
            if event == self.EVENTS[-1]:
                self.backend += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_misses":
            with self._lock:
                self.misses += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"compile_s": self.seconds, "backend_compiles": self.backend,
                    "cache_misses": self.misses}


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip, where the backend reports it."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0
