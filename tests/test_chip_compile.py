"""Compile the chip path for a described TPU v5e, with no chip attached.

The TPU's compiler is installed here and compiles for a chip that is only
described.  These tests compile, at the widths ``chip_smoke.py`` runs on the
chip, what interpret mode cannot vouch for:

- both Pallas kernels in float32 with ``interpret=False`` (block tiling,
  ref slicing and VMEM use are the compiler's to refuse);
- the float64 engine and client programs, whose TPU lowering must hold no
  sort: the TPU's compiler takes minutes for a float64 sort or ``cumsum``
  of the G_R event lists and for a sort of a 10^6-client stream.

The topology is described inside a module fixture, never at import, so every
test worker collects the same tests and only the one given this file loads
the TPU library.  The persistent compilation cache is off around these
compiles: an entry written for a described chip cannot be read back here.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.clients_segred import segment_counts
from repro.kernels.compat import enable_x64
from repro.kernels.tropical import tropical_matmul, tropical_matmul_threshold


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


# ------------------------------------------------------------ kernels (f32)

# (K * configs, n, 2n) x (2n, n): the G_R relaxation at n = 64, K = 12, and
# 16 configs
GR_A, GR_B, GR_T = (12 * 16, 64, 128), (128, 64), (12 * 16, 64, 64)


def test_tropical_matmul_compiles_for_v5e(one_chip):
    c = _compile(lambda a, b: tropical_matmul(a, b, interpret=False),
                 _sds(GR_A, jnp.float32, one_chip),
                 _sds(GR_B, jnp.float32, one_chip))
    assert "tpu_custom_call" in c.as_text()


def test_tropical_matmul_threshold_compiles_for_v5e(one_chip):
    c = _compile(lambda a, b, t: tropical_matmul_threshold(
        a, b, t, interpret=False),
        _sds(GR_A, jnp.float32, one_chip), _sds(GR_B, jnp.float32, one_chip),
        _sds(GR_T, jnp.float32, one_chip))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("m,k", [(125_000, 4096), (250_000, 78_184)])
def test_segment_counts_compiles_for_v5e(one_chip, m, k):
    """10^6 clients over 8 servers (1 and 2 requests each) against the
    kernel phase's edges and the open-loop horizon."""
    c = _compile(lambda s, e: segment_counts(s, e, interpret=False),
                 _sds((8, m), jnp.float32, one_chip),
                 _sds((8, k), jnp.float32, one_chip))
    assert "tpu_custom_call" in c.as_text()


# ------------------------------------------------ f64 programs, sort-free

def _has_op(compiled, op):
    return re.search(rf"\s{re.escape(op)}\(", compiled.as_text()) is not None


def test_engine_programs_compile_without_sort_for_v5e(one_chip):
    from repro.vecsim import engine

    n, K, B, d = 8, 12, 2, 2
    with enable_x64():
        gu = engine._compiled_unreliable(n, K, 14, "vec").lower(
            _sds((B, n, n), jnp.int32, one_chip),
            *[_sds((B, n, n), jnp.float64, one_chip)] * 3).compile()
        gr = engine._compiled_reliable(n, K, d, 76, True, "vec").lower(
            _sds((B, n, d), jnp.int32, one_chip),
            _sds((B, n, d), jnp.float64, one_chip),
            _sds((B, n, d), jnp.bool_, one_chip),
            _sds((B, n), jnp.float64, one_chip)).compile()
    for c in (gu, gr):
        assert not _has_op(c, "sort") and not _has_op(c, "reduce-window")


def test_client_pipelines_compile_without_sort_for_v5e(one_chip):
    from repro.vecsim import clients

    f64 = jnp.float64
    u32 = jnp.uint32
    fn = clients._compiled_pipeline(2, 64, "vec")
    compiled = []
    with enable_x64():
        # one per-server timeline, and 16 schedules shared by every server
        for entry in ((1, 8, 512), (16, 1, 512)):
            compiled.append(fn.lower(
                _sds(entry, f64, one_chip), _sds((8, 4096), f64, one_chip),
                (_sds(entry, u32, one_chip),) * 2,
                (_sds((8, 4096), u32, one_chip),) * 2).compile())
    for c in compiled:
        assert not _has_op(c, "sort")


def test_hlo_op_names_are_read():
    """The sort-free checks above read op names from HLO text: guard the
    parser on a CPU program that does sort."""
    c = jax.jit(lambda x: (jnp.sort(x), jnp.cumsum(x))).lower(
        np.zeros(8, np.float32)).compile()
    assert _has_op(c, "sort") and _has_op(c, "reduce-window")
