"""Tropical-kernel bench: jnp gather vs Pallas min-plus relaxation.

Pushes the same 768-config grid as ``sweep_vec`` (seeds x n x d x algorithm
x network) through ``repro.vecsim.sweep`` with both inner-relaxation
engines and cross-checks the results bit-for-bit.  Off-TPU the Pallas path
runs in interpret mode, so the emitted ratio is the *emulation overhead*,
and every row names the platform it ran on.  On a TPU the engine's Pallas
path is refused (the engine runs in float64, which Pallas on a TPU lacks);
``chip_smoke.py`` compiles and checks the kernels there in float32.
A raw-kernel microbench row compares one blocked ``tropical_matmul``
against the dense jnp broadcast min-plus it replaces.
"""
from __future__ import annotations

import time

import numpy as np

from repro.vecsim import grid, sweep

from .common import emit


def _grid():
    return grid(algo=("allconcur+", "allconcur", "allgather"),
                n=(8, 16, 32, 64), d=(2, 3), network=("sdc", "uniform"),
                seed=range(16), rounds=12)


def main(full: bool = False) -> None:
    import jax
    import jax.numpy as jnp

    cfgs = _grid()
    window = (3, 10)

    timings = {}
    results = {}
    for eng in ("vec", "pallas"):
        t0 = time.time()
        results[eng] = sweep(cfgs, window=window, engine=eng)
        cold = time.time() - t0
        t0 = time.time()
        results[eng] = sweep(cfgs, window=window, engine=eng)
        timings[eng] = (cold, time.time() - t0)

    exact = (np.array_equal(results["vec"].median_latency,
                            results["pallas"].median_latency)
             and np.array_equal(results["vec"].throughput,
                                results["pallas"].throughput))
    # name the device the rows ran on; off the TPU the Pallas path runs in
    # interpret mode and its times are not chip times
    dev = jax.devices()[0]
    where = f"platform={dev.platform};device_kind={dev.device_kind}"
    emit("tropical_sweep_768", timings["pallas"][1] / len(cfgs) * 1e6,
         f"configs={len(cfgs)};{where};bitexact={exact};"
         f"pallas_warm_s={timings['pallas'][1]:.3f};"
         f"pallas_cold_s={timings['pallas'][0]:.3f};"
         f"vec_warm_s={timings['vec'][1]:.3f};"
         f"pallas_over_vec_x={timings['pallas'][1] / timings['vec'][1]:.2f}")

    # raw kernel microbench: blocked Pallas min-plus vs dense jnp broadcast
    from repro.kernels.tropical import tropical_matmul

    m = 512 if full else 256
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.uniform(0, 10, (m, m)), jnp.float32)
    b = jnp.asarray(rng.uniform(0, 10, (m, m)), jnp.float32)
    jnp_mm = jax.jit(lambda x, y: jnp.min(x[:, :, None] + y[None], axis=1))

    def bench(fn, reps=5):
        fn(a, b).block_until_ready()            # warm / compile
        t0 = time.time()
        for _ in range(reps):
            out = fn(a, b)
        out.block_until_ready()
        return (time.time() - t0) / reps

    t_jnp = bench(jnp_mm)
    t_pal = bench(lambda x, y: tropical_matmul(x, y, block_m=128,
                                               block_n=128, block_k=128))
    same = bool((jnp_mm(a, b) == tropical_matmul(a, b)).all())
    emit("tropical_matmul_raw", t_pal * 1e6,
         f"m={m};{where};bitexact={same};"
         f"jnp_us={t_jnp*1e6:.1f};pallas_over_jnp_x={t_pal/t_jnp:.2f}")


if __name__ == "__main__":
    main(full=False)
