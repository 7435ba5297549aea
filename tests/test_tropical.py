"""Tropical (min-plus) Pallas kernel vs jnp references + vecsim parity.

The kernel's contract is *bit-for-bit* agreement with a jnp min-plus over
the same candidate set (min and broadcast-add are exact in floating point),
which is what lets ``engine="pallas"`` reproduce the vecsim engine — and
therefore the event simulator — exactly.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from repro.kernels.compat import enable_x64

from repro.kernels.tropical import (tropical_closure, tropical_matmul,
                                    tropical_matmul_threshold, tropical_relax)
from repro.vecsim import engine as vec_engine
from repro.vecsim import grid, reliable_tables, sweep, unreliable_tables

RNG = np.random.default_rng(7)


def ref_minplus(a, b):
    return np.min(np.asarray(a)[..., :, :, None]
                  + np.asarray(b)[..., None, :, :], axis=-2)


# ------------------------------------------------------------------ kernel

@pytest.mark.parametrize("m,k,n", [(5, 7, 9), (37, 41, 19), (16, 16, 16),
                                   (1, 64, 3), (8, 130, 8)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_tropical_matmul_matches_reference(m, k, n, dtype):
    with enable_x64():
        a = RNG.uniform(0, 10, (m, k)).astype(dtype)
        b = RNG.uniform(0, 10, (k, n)).astype(dtype)
        out = tropical_matmul(jnp.asarray(a), jnp.asarray(b),
                              block_m=16, block_n=16, block_k=16)
        assert out.dtype == dtype
        np.testing.assert_array_equal(np.asarray(out), ref_minplus(a, b))


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_tropical_matmul_inf_rows_and_cols(dtype):
    """+inf padding rows/cols (non-edges) must flow through exactly."""
    with enable_x64():
        a = RNG.uniform(0, 5, (9, 13)).astype(dtype)
        b = RNG.uniform(0, 5, (13, 11)).astype(dtype)
        a[2, :] = np.inf            # unreachable source row
        a[:, 5] = np.inf            # dead intermediate (column of A...)
        b[5, :] = np.inf            # ...and its row of B
        b[:, 7] = np.inf            # unreachable sink column
        out = np.asarray(tropical_matmul(jnp.asarray(a), jnp.asarray(b),
                                         block_m=4, block_n=4, block_k=4))
        ref = ref_minplus(a, b)
        np.testing.assert_array_equal(out, ref)
        assert np.isinf(out[2]).all() and np.isinf(out[:, 7]).all()


def test_tropical_matmul_batched_and_shared_b():
    with enable_x64():
        a = jnp.asarray(RNG.uniform(0, 5, (3, 2, 8, 12)))
        b_shared = jnp.asarray(RNG.uniform(0, 5, (12, 9)))
        b_batched = jnp.asarray(RNG.uniform(0, 5, (3, 2, 12, 9)))
        np.testing.assert_array_equal(
            np.asarray(tropical_matmul(a, b_shared, block_k=8)),
            ref_minplus(a, np.broadcast_to(np.asarray(b_shared),
                                           (3, 2, 12, 9))))
        np.testing.assert_array_equal(
            np.asarray(tropical_matmul(a, b_batched, block_k=8)),
            ref_minplus(a, b_batched))


def test_tropical_matmul_threshold_gates_below_big():
    """Candidates below the threshold contribute exactly ``big`` (not inf),
    replicating the vecsim G_R install rule."""
    big = 1e12
    with enable_x64():
        a = jnp.asarray(RNG.uniform(0, 5, (2, 6, 10)))
        b = jnp.asarray(RNG.uniform(0, 5, (10, 7)))
        t = jnp.asarray(RNG.uniform(4, 8, (2, 6, 7)))
        plain, gated = tropical_matmul_threshold(a, b, t, big=big, block_k=4)
        cand = np.asarray(a)[..., :, :, None] + np.asarray(b)[None, :, :]
        np.testing.assert_array_equal(np.asarray(plain),
                                      np.min(cand, axis=-2))
        gref = np.min(np.where(cand >= np.asarray(t)[..., None, :], cand,
                               big), axis=-2)
        np.testing.assert_array_equal(np.asarray(gated), gref)
        # all candidates below threshold in some cell -> exactly big
        t_hi = jnp.full_like(t, 1e6)
        _, gate_all = tropical_matmul_threshold(a, b, t_hi, big=big,
                                                block_k=4)
        assert (np.asarray(gate_all) == big).all()


def test_tropical_relax_and_closure_reach_shortest_paths():
    n = 12
    cost = RNG.uniform(1, 5, (n, n))
    cost[RNG.uniform(size=(n, n)) < 0.4] = np.inf
    np.fill_diagonal(cost, np.inf)
    dist = np.where(np.eye(n, dtype=bool), 0.0, cost)
    for k in range(n):       # Floyd-Warshall reference
        dist = np.minimum(dist, dist[:, k:k + 1] + dist[k:k + 1, :])
    with enable_x64():
        c64 = jnp.asarray(cost, jnp.float64)
        clo = np.asarray(tropical_closure(c64))
        t0 = jnp.asarray(np.where(np.eye(n, dtype=bool), 0.0, np.inf))
        rel = np.asarray(tropical_relax(t0, c64, iters=n - 1))
    np.testing.assert_allclose(clo, dist, rtol=1e-12)
    np.testing.assert_allclose(rel, dist, rtol=1e-12)


def test_compiled_kernels_refuse_float64():
    """Pallas on a TPU has no float64: asking for the compiled kernel with
    float64 data fails loudly instead of falling back to the interpreter."""
    from repro.kernels.clients_segred import segment_counts
    with enable_x64():
        a = jnp.zeros((4, 8), jnp.float64)
        b = jnp.zeros((8, 4), jnp.float64)
        with pytest.raises(ValueError, match="no float64"):
            tropical_matmul(a, b, interpret=False)
        with pytest.raises(ValueError, match="no float64"):
            tropical_matmul_threshold(a, b, jnp.zeros((4, 4)),
                                      interpret=False)
        with pytest.raises(ValueError, match="no float64"):
            segment_counts(jnp.zeros((2, 16)), jnp.zeros((2, 4)),
                           interpret=False)


def test_tropical_matmul_rejects_bad_shapes():
    with pytest.raises(ValueError):
        tropical_matmul(jnp.zeros((3, 4)), jnp.zeros((5, 6)))
    with pytest.raises(ValueError):
        tropical_matmul(jnp.zeros((2, 3, 4)), jnp.zeros((3, 4, 5)))


# ----------------------------------------------------- vecsim parity (exact)

@pytest.mark.parametrize("network", ["uniform", "sdc"])
@pytest.mark.parametrize("n", [8, 16])
def test_engine_pallas_equals_vec_exactly(n, network):
    t = unreliable_tables(n, network=network)
    a = vec_engine.run_unreliable(t.parent, t.send_off, t.occ, t.prop,
                                  rounds=6)
    b = vec_engine.run_unreliable(t.parent, t.send_off, t.occ, t.prop,
                                  rounds=6, engine="pallas")
    np.testing.assert_array_equal(a.completion, b.completion)
    np.testing.assert_array_equal(a.start, b.start)

    tr = reliable_tables(n, network=network)
    c = vec_engine.run_reliable(tr.adj, tr.edge_off, tr.occ, tr.prop,
                                rounds=6)
    d = vec_engine.run_reliable(tr.adj, tr.edge_off, tr.occ, tr.prop,
                                rounds=6, engine="pallas")
    np.testing.assert_array_equal(c.completion, d.completion)
    np.testing.assert_array_equal(c.start, d.start)


def test_sweep_engine_pallas_equals_vec_exactly():
    cfgs = grid(algo=("allconcur+", "allconcur", "allgather"), n=(8,),
                network=("uniform", "sdc"), rounds=6)
    a = sweep(cfgs, window=(2, 4))
    b = sweep(cfgs, window=(2, 4), engine="pallas")
    np.testing.assert_array_equal(a.median_latency, b.median_latency)
    np.testing.assert_array_equal(a.throughput, b.throughput)
    np.testing.assert_array_equal(a.round_period, b.round_period)


def test_engine_rejects_unknown_engine():
    t = unreliable_tables(8, network="uniform")
    with pytest.raises(ValueError):
        vec_engine.run_unreliable(t.parent, t.send_off, t.occ, t.prop,
                                  rounds=2, engine="tpu")
