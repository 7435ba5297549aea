"""vecsim: cross-validation against the event engine + unit tests.

The cross-validation class is the subsystem's acceptance gate: failure-free
round latency and windowed throughput from the vectorized min-plus engine
must match the discrete-event `Simulation` within 1% (they match to float
precision in practice, because vecsim replicates the event semantics).
"""
import numpy as np
import pytest

from repro.sim import build_simulation
from repro.vecsim import (SweepConfig, grid, monte_carlo, reliable_tables,
                          sweep, unreliable_tables)
from repro.vecsim import engine as vec_engine

ROUNDS = 10
WINDOW = (3, 8)


def run_event(algo, n, network, *, batch=4, rounds=ROUNDS):
    sim, met = build_simulation(algo, n, batch=batch, network=network)
    sim.start()
    target = rounds * n
    sim.run(until=lambda: len(met.delivered_msgs) >= n and
            all(v >= target for v in met.delivered_msgs.values()),
            max_time=60.0)
    return met


def run_vec(algo, n, network, *, batch=4, rounds=ROUNDS, engine="vec"):
    if algo == "allconcur":
        t = reliable_tables(n, network=network, batch=batch)
        rt = vec_engine.run_reliable(t.adj, t.edge_off, t.occ, t.prop,
                                     rounds=rounds, engine=engine)
    else:
        t = unreliable_tables(n, network=network, batch=batch, mode=algo)
        rt = vec_engine.run_unreliable(t.parent, t.send_off, t.occ, t.prop,
                                       rounds=rounds, engine=engine)
    return vec_engine.summarize(rt, mode=algo, n=n, batch=batch,
                                window=WINDOW)


# ------------------------------------------------------ cross-validation

class TestCrossValidation:
    @pytest.mark.parametrize("network", ["uniform", "sdc"])
    @pytest.mark.parametrize("n", [8, 16])
    @pytest.mark.parametrize("algo", ["allconcur+", "allconcur", "allgather"])
    def test_latency_and_throughput_within_1pct(self, algo, n, network):
        met = run_event(algo, n, network)
        s = run_vec(algo, n, network)
        ev_lat, ev_thr = met.median_latency(), met.throughput(*WINDOW)
        v_lat, v_thr = float(s["median_latency"]), float(s["throughput"])
        assert abs(v_lat - ev_lat) <= 0.01 * ev_lat, (
            f"latency: event {ev_lat:.6e} vs vec {v_lat:.6e}")
        assert abs(v_thr - ev_thr) <= 0.01 * ev_thr, (
            f"throughput: event {ev_thr:.0f} vs vec {v_thr:.0f}")

    @pytest.mark.parametrize("algo", ["allconcur+", "allconcur", "allgather"])
    def test_pallas_engine_matches_event_sim(self, algo):
        """The tropical-kernel lowering reproduces the event simulator just
        like the jnp path does (it is bit-for-bit equal to it)."""
        met = run_event(algo, 8, "sdc")
        s = run_vec(algo, 8, "sdc", engine="pallas")
        ev_lat, ev_thr = met.median_latency(), met.throughput(*WINDOW)
        assert abs(float(s["median_latency"]) - ev_lat) <= 0.01 * ev_lat
        assert abs(float(s["throughput"]) - ev_thr) <= 0.01 * ev_thr


# ---------------------------------------------------------------- topology

def test_unreliable_tables_are_a_spanning_tree_per_source():
    t = unreliable_tables(12, network="uniform")
    n = t.n
    for s in range(n):
        # every server reachable from s by following parents backwards
        for v in range(n):
            hops, cur = 0, v
            while cur != s:
                cur = int(t.parent[s, cur])
                hops += 1
                assert hops <= n, f"parent cycle for src={s}, v={v}"
    # total relays per message = n - 1 (minimal work)
    assert np.isclose(t.occ.sum(axis=1), (n - 1) * t.ser).all()


def test_reliable_tables_match_gr_degree():
    t = reliable_tables(16, d=3, network="sdc")
    assert t.adj.sum(axis=1).tolist() == [3] * 16
    assert np.isclose(t.occ, 3 * t.ser).all()
    # edge_off encodes the NIC send order: 1..d slots of one serialization
    offs = np.sort(t.edge_off[0][t.adj[0]])
    assert np.allclose(offs, t.ser * np.arange(1, 4))


def test_message_bytes_is_encoded_frame_length():
    """Cost tables are built from *encoded* lengths: message_bytes must be
    exactly len(encode(probe)) for every mode/batch — byte-accounting parity
    between vecsim, the event sim and the real codec."""
    from repro.core.messages import Message, MsgKind
    from repro.vecsim import message_bytes
    from repro.wire import encode
    for mode in ("allconcur+", "allconcur", "allgather"):
        kind = MsgKind.RBCAST if mode == "allconcur" else MsgKind.BCAST
        for batch in (1, 4, 32):
            probe = Message(kind, 0, 1, 1, payload={"batch": batch})
            assert message_bytes(mode, batch) == len(encode(probe))


def test_frame_length_invariant_in_round_and_src():
    """vecsim charges ONE per-message size per config, so the encoded length
    must not depend on which round/server produced the message (fixed-width
    header counters) — else long event-sim runs would drift off the tables."""
    from repro.core.messages import Message, MsgKind
    from repro.wire import encode
    ref = len(encode(Message(MsgKind.BCAST, 0, 1, 1, payload={"batch": 4})))
    for src, epoch, rnd in [(63, 1, 64), (127, 200, 10**6), (0, 2**31, 2**63)]:
        m = Message(MsgKind.BCAST, src, epoch, rnd, payload={"batch": 4})
        assert len(encode(m)) == ref


def test_cost_tables_cross_validate_exactly_with_encoded_lengths():
    """With ser times derived from encoded lengths, the vectorized engine
    still reproduces the event simulator *exactly* (0.0000%), not just
    within the 1% gate above."""
    for algo in ("allconcur+", "allconcur", "allgather"):
        met = run_event(algo, 8, "sdc")
        s = run_vec(algo, 8, "sdc")
        np.testing.assert_allclose(float(s["median_latency"]),
                                   met.median_latency(), rtol=1e-12)
        np.testing.assert_allclose(float(s["throughput"]),
                                   met.throughput(*WINDOW), rtol=1e-12)


# ------------------------------------------------------------------ engine

def test_rounds_are_monotone_and_batched_equals_single():
    t = unreliable_tables(8, network="sdc")
    rt = vec_engine.run_unreliable(t.parent, t.send_off, t.occ, t.prop,
                                   rounds=6)
    C = rt.completion
    assert C.shape == (6, 8)
    assert (np.diff(C, axis=0) > 0).all()
    # stacking the same config twice gives identical per-lane results
    def stack(a):
        return np.stack([a, a])

    rt2 = vec_engine.run_unreliable(stack(t.parent), stack(t.send_off),
                                    stack(t.occ), stack(t.prop), rounds=6)
    assert rt2.completion.shape == (2, 6, 8)
    np.testing.assert_allclose(rt2.completion[0], C)
    np.testing.assert_allclose(rt2.completion[1], C)


def test_summarize_window_fallback_matches_event_metrics():
    # fewer rounds than the window needs: throughput falls back to the last
    # deliver event exactly like Metrics.window does
    t = unreliable_tables(8, network="uniform")
    rt = vec_engine.run_unreliable(t.parent, t.send_off, t.occ, t.prop,
                                   rounds=4)
    s = vec_engine.summarize(rt, mode="allgather", n=8, batch=4,
                             window=(2, 100))
    assert np.isfinite(s["throughput"])
    s2 = vec_engine.summarize(rt, mode="allgather", n=8, batch=4,
                              window=(4, 100))  # t1 == t2 == last event
    assert np.isnan(s2["throughput"])


# ------------------------------------------------------------------- sweep

def test_sweep_groups_and_orders_results():
    cfgs = grid(algo=("allconcur+", "allconcur"), n=(8,),
                network=("uniform", "sdc"), seed=range(2), rounds=6)
    assert len(cfgs) == 8
    res = sweep(cfgs, window=(2, 4))
    assert np.isfinite(res.median_latency).all()
    assert np.isfinite(res.throughput).all()
    # results align with config order: same (algo, network) across seeds is
    # identical (failure-free rounds are seed-independent)
    by_key = {}
    for i, c in enumerate(cfgs):
        by_key.setdefault((c.algo, c.network), []).append(res.throughput[i])
    for vals in by_key.values():
        assert len(set(np.round(vals, 6))) == 1
    # dual mode trades ~2x latency for AllGather-level throughput
    i_plus = cfgs.index(SweepConfig(algo="allconcur+", n=8, network="sdc",
                                    rounds=6))
    i_rel = cfgs.index(SweepConfig(algo="allconcur", n=8, network="sdc",
                                   rounds=6))
    assert res.throughput[i_plus] > 1.5 * res.throughput[i_rel]


def test_sweep_matches_standalone_engine():
    cfg = SweepConfig(algo="allconcur", n=8, network="sdc", rounds=ROUNDS)
    res = sweep([cfg], window=WINDOW)
    s = run_vec("allconcur", 8, "sdc")
    np.testing.assert_allclose(res.median_latency[0], s["median_latency"])
    np.testing.assert_allclose(res.throughput[0], s["throughput"])


# ---------------------------------------------------------------- failures

def test_monte_carlo_failure_free_limit_and_degradation():
    du, dr = 100e-6, 300e-6
    # mtbf >> horizon: no crashes land; throughput = n*batch/du, latency 2du
    mc0 = monte_carlo(du, dr, n=8, batch=4, mtbf=1e6, rounds=50,
                      n_schedules=64, seed=0)
    assert mc0.crashes.max() == 0
    np.testing.assert_allclose(mc0.throughput, 8 * 4 / du, rtol=1e-9)
    np.testing.assert_allclose(mc0.mean_latency, 2 * du, rtol=1e-9)
    # frequent crashes strictly degrade expectation
    mc1 = monte_carlo(du, dr, n=8, batch=4, mtbf=20 * du, rounds=50,
                      n_schedules=256, seed=1)
    assert mc1.crashes.mean() > 0
    assert mc1.throughput.mean() < mc0.throughput.mean()
    assert mc1.mean_latency.mean() > mc0.mean_latency.mean()
    # deterministic given the seed
    mc1b = monte_carlo(du, dr, n=8, batch=4, mtbf=20 * du, rounds=50,
                       n_schedules=256, seed=1)
    np.testing.assert_array_equal(mc1.throughput, mc1b.throughput)


def test_monte_carlo_back_to_back_crashes_stay_positive():
    """A crash sampled inside the previous recovery window must not produce
    negative latency or super-unit throughput (regression: the splice used
    the raw crash time even when it predated the round start)."""
    du, dr = 100e-6, 300e-6
    mc = monte_carlo(du, dr, n=8, batch=4, mtbf=du / 2, rounds=30,
                     n_schedules=512, seed=3, fd_timeout=10e-3)
    assert (mc.mean_latency > 0).all()
    assert (np.diff(mc.total_time) != 0).any() or mc.total_time[0] > 0
    # every schedule is slower than failure-free, never faster
    assert (mc.throughput <= 8 * 4 / du + 1e-6).all()


def test_sweep_empty_returns_empty_result():
    res = sweep([])
    assert res.configs == []
    assert res.throughput.shape == (0,)


def test_monte_carlo_eon_splice_exact_failure_free():
    """§III-I eon transitions in the Monte-Carlo splice: the transitional
    round is one reliable round on the old tables; later rounds draw from
    the post-flip tables and membership."""
    du, dr = 100e-6, 300e-6
    # identical tables: exactly one du replaced by dr
    base = monte_carlo(du, dr, n=16, batch=8, mtbf=1e9, rounds=50,
                       n_schedules=4, seed=0)
    flip = monte_carlo(du, dr, n=16, batch=8, mtbf=1e9, rounds=50,
                       n_schedules=4, seed=0, eon_round=20)
    np.testing.assert_allclose(base.total_time, 50 * du, rtol=1e-12)
    np.testing.assert_allclose(flip.total_time, 49 * du + dr, rtol=1e-12)
    # topology swap: slower post-flip rounds and one extra member
    du2, dr2 = 150e-6, 450e-6
    sw = monte_carlo(du, dr, n=16, batch=8, mtbf=1e9, rounds=50,
                     n_schedules=4, seed=0, eon_round=20,
                     du2_by_f=[du2] * 5, dr2_by_f=[dr2] * 5, n2=17)
    exp_t = 20 * du + dr + 29 * du2
    np.testing.assert_allclose(sw.total_time, exp_t, rtol=1e-12)
    msgs = 21 * 16 + 29 * 17
    np.testing.assert_allclose(sw.throughput, msgs * 8 / exp_t, rtol=1e-12)


def test_monte_carlo_eon_splice_composes_with_crashes():
    du, dr = 100e-6, 300e-6
    mc = monte_carlo(du, dr, n=16, batch=8, mtbf=5e-3, rounds=100,
                     n_schedules=512, seed=1, eon_round=30,
                     du2_by_f=[120e-6] * 5, dr2_by_f=[350e-6] * 5, n2=17)
    assert np.isfinite(mc.throughput).all()
    assert (mc.mean_latency > 0).all()
    assert (mc.total_time > 0).all()
    # disabling the splice reproduces the original recurrence bit-for-bit
    a = monte_carlo(du, dr, n=16, batch=8, mtbf=5e-3, rounds=100,
                    n_schedules=512, seed=1)
    b = monte_carlo(du, dr, n=16, batch=8, mtbf=5e-3, rounds=100,
                    n_schedules=512, seed=1, du2_by_f=[1.0] * 5,
                    dr2_by_f=[1.0] * 5, n2=99)   # ignored without eon_round
    np.testing.assert_array_equal(a.throughput, b.throughput)
    np.testing.assert_array_equal(a.mean_latency, b.mean_latency)


def test_monte_carlo_eon_round_bounds_validated():
    import pytest
    with pytest.raises(ValueError):
        monte_carlo(1e-4, 3e-4, n=8, batch=4, mtbf=1.0, rounds=10,
                    n_schedules=2, eon_round=10)


def test_tpu_nic_scan_order_and_times_match_sorted_scan():
    """The TPU lowering of the NIC scan (comparison-count ranks, associative
    prefix scans) replays the same FIFO as the lexsort path.  With integer
    occupancies and times every prefix sum is exact in any order, so the two
    agree bit for bit; ties exercise the stable tie-break."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.compat import enable_x64

    rng = np.random.default_rng(5)
    with enable_x64():
        for m in (1, 7, 96, 384):
            E = jnp.asarray(rng.integers(0, 12, m).astype(np.float64))
            r = jnp.asarray(rng.integers(0, 3, m).astype(np.float64))
            occ = jnp.asarray(rng.integers(0, 4, m).astype(np.float64))
            tx0 = jnp.float64(rng.integers(0, 5))
            tpu = jax.jit(lambda *a: vec_engine._nic_scan_tpu(jnp, *a))
            ref = jax.jit(lambda *a: vec_engine._nic_scan_sorted(jnp, *a))
            st, ft = tpu((r, E), occ, tx0)
            sr, fr = ref((r, E), occ, tx0)
            np.testing.assert_array_equal(np.asarray(st), np.asarray(sr))
            assert float(ft) == float(fr)


def test_monte_carlo_long_horizon_clock_keeps_contract():
    """The splice clock is a compensated sum: over 52144 rounds (the 10^6
    client crash-schedule horizon) a plain float64 sum of this round length
    drifts 1.1e-12 from rounds x du, past the 1e-12 timeline contract."""
    du, rounds = 0.00022202799999999998, 52144
    mc = monte_carlo(du, 3 * du, n=8, batch=64, mtbf=1e12, rounds=rounds,
                     n_schedules=4, seed=7)
    assert mc.crashes.max() == 0
    np.testing.assert_allclose(mc.total_time, rounds * du, rtol=1e-12)
