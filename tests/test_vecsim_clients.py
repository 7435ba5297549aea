"""Vectorized SMR client layer: exact cross-validation + unit tests.

The acceptance gate for ``repro.vecsim.clients``: given per-server round
timelines, the tensorized batch-formation/ack mapping must reproduce the
event simulator's ack times **bit-for-bit** (zero tolerance).  The exact
check runs on event-*extracted* timelines (entry/completion recorded at the
simulator's own floats, so the gathered ack is the identical float); the
full-stack check against :mod:`repro.vecsim.engine` timelines asserts the
engine's established cross-validation tolerance instead (float association
in the NIC scans costs ~1e-15 relative).

Also here: jnp-vs-Pallas bitexactness of the segment-reduce kernel, the
shared nearest-rank percentile rule, the zipfian boundary-draw regression,
the open-loop ``WorkloadConfig`` guard, and seeded determinism across
jit/vmap boundaries.
"""
import random

import numpy as np
import pytest

from repro.sim.runner import build_smr_simulation
from repro.smr.percentiles import nearest_rank, nearest_rank_index
from repro.smr.workload import WorkloadConfig, ZipfianGenerator
from repro.vecsim.clients import (arrival_times, client_latencies,
                                  closed_loop_latencies, keys_from_uniform,
                                  mc_client_latencies, server_streams,
                                  smr_round_times, zipf_cdf)
from repro.vecsim.eventref import (event_timelines, run_open_loop,
                                   server_fifo)
from repro.vecsim.failures import monte_carlo, monte_carlo_times

MODES = ("allconcur+", "allconcur", "allgather")


# ------------------------------------------- exact event cross-validation

class TestEventExactness:
    @pytest.mark.parametrize("n", [8, 16])
    @pytest.mark.parametrize("algo", MODES)
    def test_open_loop_acks_bit_for_bit(self, algo, n):
        entries, compl, subs, acks = run_open_loop(algo, n)
        e, c = event_timelines(entries, compl, n)
        by_server, s = server_fifo(subs, acks, n)
        res = client_latencies(e, c, s, mode=algo, batch_max=2)
        checked = 0
        for h in range(n):
            for j, u in enumerate(by_server[h]):
                if u not in acks or not res.valid[h, j]:
                    continue
                assert res.ack[h, j] == acks[u], (algo, n, h, u)
                assert res.latency[h, j] == acks[u] - subs[u]
                checked += 1
        assert checked >= n * 12  # nearly all requests land inside K rounds

    def test_open_loop_overflow_backlog_exact(self):
        # burst arrivals far above per-round capacity: requests queue across
        # many rounds, partially-filled DUAL batches absorb later arrivals
        for algo in MODES:
            entries, compl, subs, acks = run_open_loop(
                algo, 8, rpc=10, batch_max=2, rate=80000.0)
            e, c = event_timelines(entries, compl, 8)
            by_server, s = server_fifo(subs, acks, 8)
            res = client_latencies(e, c, s, mode=algo, batch_max=2)
            for h in range(8):
                for j, u in enumerate(by_server[h]):
                    if u in acks and res.valid[h, j]:
                        assert res.ack[h, j] == acks[u], (algo, h, u)

    @pytest.mark.parametrize("algo", MODES)
    def test_closed_loop_full_stack_engine_precision(self, algo):
        # closed-loop lockstep over *engine* timelines with SMR-sized cost
        # tables: the model is exact, the timeline itself carries the
        # engine's float-association residue — assert its 1e-12 contract
        n, cps, r = 8, 2, 6
        cfg = WorkloadConfig(read_ratio=0.0, distribution="uniform",
                             nkeys=64, num_clients=cps * n, value_size=16,
                             linearizable_reads=True, arrival="closed",
                             seed=0)
        sim, smr, _services = build_smr_simulation(
            algo, n, workload=cfg, requests_per_client=r + 8,
            batch_max=cps, network="sdc")
        subs, acks = {}, {}
        o_sub, o_ack = smr.on_submit, smr.on_ack

        def on_submit(uid, t):
            subs.setdefault(uid, t)
            o_sub(uid, t)

        def on_ack(uid, t, is_read):
            acks.setdefault(uid, t)
            o_ack(uid, t, is_read)

        smr.on_submit, smr.on_ack = on_submit, on_ack
        gen = sim.workload
        sim.start()
        sim.run(until=lambda: all(c.acked >= r for c in gen.clients),
                max_time=30.0)
        dual = algo == "allconcur+"
        times = smr_round_times(algo, n, reqs_per_round=cps,
                                rounds=2 * r + 2 if dual else r + 1)
        lat = closed_loop_latencies(times, mode=algo, batch_max=cps,
                                    clients_per_server=cps)
        for cid in range(cps * n):
            for g in range(r):
                # gen-0 submits are primed at t=0 before metrics attach
                ev = acks[(cid, g)] - subs.get((cid, g), 0.0)
                np.testing.assert_allclose(lat[g, cid % n], ev, rtol=1e-12)

    def test_closed_loop_requires_lockstep(self):
        times = smr_round_times("allgather", 8, reqs_per_round=2, rounds=6)
        with pytest.raises(ValueError, match="lockstep"):
            closed_loop_latencies(times, mode="allgather", batch_max=2,
                                  clients_per_server=3)


# ------------------------------------------------- jnp vs Pallas bitexact

class TestPallasBitexact:
    def test_segment_counts_matches_reference(self):
        from repro.kernels import segment_counts, segment_counts_reference
        rng = np.random.default_rng(0)
        for shape_s, shape_e in [((37,), (11,)), ((3, 200), (3, 130)),
                                 ((2, 2, 50), (2, 2, 257)),
                                 ((2, 3000), (2, 70))]:   # 3 client blocks
            s = rng.uniform(0, 1, shape_s)
            s.flat[::7] = np.inf                       # ragged padding
            s.flat[1] = 0.5                            # exact ties at an edge
            e = np.sort(rng.uniform(0, 1, shape_e), axis=-1)
            e.flat[shape_e[-1] // 2] = 0.5
            e = np.sort(e, axis=-1)                    # keep edges ascending
            ref = np.asarray(segment_counts_reference(s, e))
            ker = np.asarray(segment_counts(s, e, block_k=64, block_m=32))
            brute = (s[..., :, None] <= e[..., None, :]).sum(-2)
            assert (ref == brute).all()
            assert (ker == ref).all()

    def test_order_key_counts_are_exact(self):
        """The "vec" counts compare exact order words, not float values:
        times one ulp apart, ties, +inf padding and -0.0 all count as the
        float comparison does on the host."""
        import jax.numpy as jnp

        from repro.kernels import segment_counts_reference
        from repro.kernels.compat import enable_x64
        from repro.vecsim.clients import _ascending_counts, order_keys

        rng = np.random.default_rng(3)
        base = np.sort(rng.uniform(1e-4, 1e-2, (3, 400)), axis=1)
        s = np.sort(np.concatenate([base, np.nextafter(base, 1.0),
                                    np.full((3, 9), np.inf)], axis=1),
                    axis=1)
        s[0, 0] = -0.0
        e = np.sort(np.concatenate([base[:, ::7], np.nextafter(
            base[:, 3::11], 0.0), np.zeros((3, 1))], axis=1), axis=1)
        with enable_x64():
            got = np.asarray(_ascending_counts(
                tuple(map(jnp.asarray, order_keys(s))),
                tuple(map(jnp.asarray, order_keys(e)))))
            ref = np.asarray(segment_counts_reference(s, e))
        assert (got == ref).all()
        assert (got == (s[:, :, None] <= e[:, None, :]).sum(1)).all()

    def test_segment_counts_under_vmap(self):
        import jax
        from repro.kernels import segment_counts, segment_counts_reference
        rng = np.random.default_rng(1)
        s = rng.uniform(0, 1, (5, 4, 64))
        e = np.sort(rng.uniform(0, 1, (5, 4, 33)), axis=-1)
        ker = jax.vmap(lambda a, b: segment_counts(a, b, block_k=16,
                                                   block_m=16))(s, e)
        assert (np.asarray(ker)
                == np.asarray(segment_counts_reference(s, e))).all()

    @pytest.mark.parametrize("algo", MODES)
    def test_client_pipeline_engines_agree(self, algo):
        times = smr_round_times(algo, 8, reqs_per_round=4, rounds=20)
        s = server_streams(arrival_times(3, 32, 5, rate=8000.0), 8)
        e = np.asarray(times.start).T
        c = np.asarray(times.completion).T
        rv = client_latencies(e, c, s, mode=algo, batch_max=4, engine="vec")
        rp = client_latencies(e, c, s, mode=algo, batch_max=4,
                              engine="pallas")
        assert (rv.round_idx == rp.round_idx).all()
        assert (rv.ack == rp.ack).all()
        assert (rv.valid == rp.valid).all()
        assert rv.percentiles == rp.percentiles
        assert rv.served == rp.served


# ------------------------------------------------------- percentile rule

class TestPercentiles:
    def test_small_n_edge_cases(self):
        assert nearest_rank([7.0], 0.5) == 7.0
        assert nearest_rank([7.0], 0.999) == 7.0
        assert nearest_rank([2.0, 1.0], 0.5) == 2.0      # int(0.5*2)=1
        assert nearest_rank([2.0, 1.0], 0.99) == 2.0
        assert nearest_rank([3.0, 1.0, 2.0], 0.5) == 2.0
        assert nearest_rank([3.0, 1.0, 2.0], 0.999) == 3.0
        xs = [5.0, 4.0, 3.0, 2.0, 1.0]
        assert nearest_rank(xs, 0.5) == 3.0
        assert nearest_rank(xs, 0.99) == 5.0
        assert np.isnan(nearest_rank([], 0.5))
        with pytest.raises(ValueError):
            nearest_rank_index(0, 0.5)

    def test_matches_smr_metrics_rule(self):
        from repro.sim.runner import SMRMetrics
        rng = random.Random(0)
        for size in (1, 2, 3, 7, 100, 1001):
            xs = [rng.random() for _ in range(size)]
            for p in (0.5, 0.99, 0.999):
                assert SMRMetrics._pct(xs, p) == nearest_rank(xs, p)

    def test_vectorized_pipeline_matches_helper(self):
        # the jit percentile gather must equal the Python helper on the
        # exact same served-latency multiset, bit for bit
        times = smr_round_times("allconcur+", 8, reqs_per_round=4, rounds=24)
        s = server_streams(arrival_times(5, 32, 6, rate=9000.0), 8)
        res = client_latencies(np.asarray(times.start).T,
                               np.asarray(times.completion).T, s,
                               mode="allconcur+", batch_max=4)
        served = [float(x) for x in res.latency[res.valid]]
        assert res.served == len(served) > 0
        for p in (0.5, 0.99, 0.999):
            assert res.percentiles[p] == nearest_rank(served, p)


# ------------------------------------------------------- workload fixes

class TestZipfianBoundary:
    def test_default_cdf_falls_short_of_one(self):
        # the trigger condition for the historical out-of-range draw: the
        # float CDF of the *default* workload config tops out below 1.0
        z = ZipfianGenerator(256, 0.99)
        assert z._cdf[-1] < 1.0

    def test_boundary_draw_clamped(self):
        class TopRng(random.Random):
            def random(self):
                return 0.9999999999999999       # largest float < 1.0

        z = ZipfianGenerator(256, 0.99)
        assert z.draw(TopRng()) == 255          # was 256 before the clamp

    def test_vectorized_clamp_mirrors_event_path(self):
        cdf = zipf_cdf(256, 0.99)
        keys = np.asarray(keys_from_uniform(
            np.array([0.0, 0.5, float(cdf[-1]), 0.9999999999999999]), cdf))
        assert keys[0] == 0
        assert (keys < 256).all()
        assert keys[-1] == 255
        # agreement with the event generator away from the boundary
        z = ZipfianGenerator(256, 0.99)
        rng = random.Random(7)
        us = [rng.random() for _ in range(500)]
        expected = [min(np.searchsorted(z._cdf, u, side="left"), 255)
                    for u in us]
        assert list(np.asarray(keys_from_uniform(np.array(us), cdf))) \
            == expected


class TestWorkloadConfigGuard:
    def test_open_rate_zero_rejected_at_construction(self):
        with pytest.raises(ValueError, match="open_rate"):
            WorkloadConfig(arrival="open", open_rate=0.0)
        with pytest.raises(ValueError, match="open_rate"):
            WorkloadConfig(arrival="open", open_rate=-5.0)

    def test_closed_loop_ignores_open_rate(self):
        WorkloadConfig(arrival="closed", open_rate=0.0)  # no raise

    def test_bad_arrival_rejected(self):
        with pytest.raises(ValueError, match="arrival"):
            WorkloadConfig(arrival="poisson")

    def test_vectorized_rate_guard(self):
        with pytest.raises(ValueError, match="rate"):
            arrival_times(0, 8, 2, rate=0.0)


# ------------------------------------------------------ seeded determinism

class TestDeterminism:
    def test_arrival_times_reproducible_and_population_invariant(self):
        a = arrival_times(42, 64, 3, rate=1000.0)
        b = arrival_times(42, 64, 3, rate=1000.0)
        assert (a == b).all()
        # per-client fold_in counters: client streams don't shift when the
        # population grows
        big = arrival_times(42, 128, 3, rate=1000.0)
        assert (big[:64] == a).all()
        assert (np.diff(a, axis=1) > 0).all()

    def test_arrival_times_match_scalar_fold_in(self):
        # the vmapped batch equals one jitted scalar draw per client, bit
        # for bit: per-client fold_in counters, no cross-client state.
        # (eager mode is excluded on purpose — XLA fusion may round the
        # exponential transform differently by 1 ulp vs the eager op-by-op
        # path, and bit parity is only promised within compiled code)
        import jax
        import jax.numpy as jnp
        from repro.kernels.compat import enable_x64
        a = arrival_times(9, 8, 4, rate=2000.0)
        with enable_x64():
            base = jax.random.PRNGKey(9)

            @jax.jit
            def one(cid):
                gaps = jax.random.exponential(
                    jax.random.fold_in(base, cid), (4,),
                    dtype=jnp.float64) / 2000.0
                return jnp.cumsum(gaps)

            for cid in range(8):
                assert (np.asarray(one(cid)) == a[cid]).all()

    def test_server_streams_round_robin(self):
        arr = np.arange(12, dtype=np.float64).reshape(6, 2)
        s = server_streams(arr, 3)
        # cid % 3 homes: server 0 <- cids 0, 3
        assert (s[0] == np.sort(np.concatenate([arr[0], arr[3]]))).all()
        with pytest.raises(ValueError, match="multiple"):
            server_streams(arr, 4)


# ------------------------------------------------------ Monte-Carlo path

class TestMonteCarloClients:
    def test_timeline_export_consistent_with_aggregate(self):
        kw = dict(n=8, batch=16, mtbf=0.05, rounds=128, n_schedules=32,
                  seed=3)
        mct = monte_carlo_times(120e-6, 180e-6, **kw)
        mc = monte_carlo(120e-6, 180e-6, **kw)
        assert mct.entry.shape == mct.deliver.shape == (32, 128)
        assert (np.diff(mct.entry, axis=1) > 0).all()
        assert (mct.deliver > mct.entry).all()
        assert (mct.crashes == mc.crashes).all()
        assert (mct.total_time == mc.total_time).all()
        # same splice: the aggregate's mean latency is the alive-weighted
        # mean of the exported per-round latencies; unweighted means agree
        # loosely (weights vary by at most max_failures servers)
        per_round = (mct.deliver - mct.entry).mean()
        assert abs(per_round - mc.mean_latency.mean()) < 0.2 * per_round

    def test_mc_client_latencies_pooled(self):
        mct = monte_carlo_times(120e-6, 180e-6, n=8, batch=16, mtbf=0.05,
                                rounds=256, n_schedules=16, seed=3)
        s = server_streams(arrival_times(0, 256, 2, rate=2 / 0.01), 8)
        res = mc_client_latencies(mct.entry, mct.deliver, s,
                                  mode="allconcur+", batch_max=16)
        assert res["schedules"] == 16
        assert 0 < res["served"] <= 16 * 512
        pct = res["percentiles"]
        assert 0 < pct[0.5] <= pct[0.99] <= pct[0.999]
        # engines agree bit-for-bit here too
        res_p = mc_client_latencies(mct.entry, mct.deliver, s,
                                    mode="allconcur+", batch_max=16,
                                    engine="pallas")
        assert res == res_p

    def test_mc_pool_equals_per_schedule_client_latencies(self):
        """The pooled run is client_latencies per schedule (the spliced
        timeline shared by every server, no extra ack lag), pooled: the
        same served count and percentiles, exactly."""
        mct = monte_carlo_times(120e-6, 180e-6, n=8, batch=16, mtbf=0.05,
                                rounds=256, n_schedules=6, seed=5)
        s = server_streams(arrival_times(2, 256, 2, rate=2 / 0.01), 8)
        res = mc_client_latencies(mct.entry, mct.deliver, s,
                                  mode="allconcur", batch_max=16)
        lats = []
        for e, d in zip(mct.entry, mct.deliver):
            one = client_latencies(np.tile(e, (8, 1)), np.tile(d, (8, 1)), s,
                                   mode="allconcur", batch_max=16, ack_lag=0)
            lats.append(one.latency[one.valid])
        pool = np.sort(np.concatenate(lats))
        assert res["served"] == pool.size > 0
        for p, v in res["percentiles"].items():
            assert v == pool[min(int(p * pool.size), pool.size - 1)]

    @pytest.mark.parametrize("size", [0, 1, 2, 17, 1000])
    def test_pooled_percentiles_equal_sorted_nearest_rank(self, size):
        """In-place partition picks the element a full sort would, with
        ties and repeated ranks; NaN when nothing was served."""
        from repro.vecsim.clients import _pooled_percentiles

        rng = np.random.default_rng(size)
        x = rng.exponential(1e-3, size)
        x[::3] = np.round(x[::3], 5)                      # ties
        ps = (0.0, 0.5, 0.5, 0.99, 0.999, 1.0)
        got = _pooled_percentiles(x.copy(), ps)
        srt = np.sort(x)
        for p in ps:
            if size == 0:
                assert np.isnan(got[p])
            else:
                assert got[p] == srt[min(int(p * size), size - 1)]
