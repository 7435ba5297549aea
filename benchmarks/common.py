"""Shared helpers for the paper benchmarks."""
from __future__ import annotations

import os
import time
from typing import Optional

from repro.sim import build_simulation


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def set_compile_cache() -> str:
    """Place JAX's persistent compilation cache; call once before compiling.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing is changed.  Otherwise the cache goes to the fixed path
    ``<repo>/.jax_cache`` (a moving path never hits).  Returns the directory.
    """
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO_ROOT, ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def run_sim(algo: str, n: int, *, batch: int = 4, network: str = "sdc",
            rounds: int = 15, max_time: float = 60.0, d: Optional[int] = None,
            crash=None):
    """Run one simulated deployment; return (metrics, wall_seconds)."""
    t0 = time.time()
    sim, met = build_simulation(algo, n, batch=batch, network=network, d=d)
    if crash:
        for c in crash:
            sim.schedule_crash(*c)
    sim.start()
    target = rounds * n
    sim.run(until=lambda: len(met.delivered_msgs) >= max(n - len(crash or ()), 1)
            and all(v >= target for v in met.delivered_msgs.values()),
            max_time=max_time)
    return met, time.time() - t0


_ROWS: list = []


def emit(name: str, us_per_call: float, derived: str) -> None:
    """CSV row: name,us_per_call,derived.  Rows are also recorded so
    ``benchmarks.run --json`` can dump them."""
    print(f"{name},{us_per_call:.3f},{derived}")
    row = {"name": name, "us_per_call": round(us_per_call, 3)}
    for part in derived.split(";"):
        if "=" in part:
            k, v = part.split("=", 1)
            try:
                row[k] = float(v)
            except ValueError:
                row[k] = v
    _ROWS.append(row)


def rows() -> list:
    """All rows emitted so far (for --json output)."""
    return list(_ROWS)
