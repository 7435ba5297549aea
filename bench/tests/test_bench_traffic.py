"""The traffic generator: deterministic in the seed, distinct per answer,
the same work for every seed."""
import json

import numpy as np
import pytest

from bench.harness import spec
from bench.harness import traffic as gen

BIG_SEED = 2 ** 31 + 987654321


def _traffic(name):
    return spec.cell(name).traffic


@pytest.mark.parametrize("workload", ["sweep_n64"])
def test_sweep_queries(workload):
    cell = spec.cell(workload)
    c, t = cell.config, cell.traffic
    lo, hi = c["batch_range"]
    blocks = (hi - lo + 1) // t["per_query"]
    q = [gen.query_batches(c, t, BIG_SEED, i) for i in range(blocks)]
    assert q == [gen.query_batches(c, t, BIG_SEED, i) for i in range(blocks)]
    assert all(len(set(b)) == t["per_query"] for b in q)
    flat = [b for blk in q for b in blk]
    assert len(set(flat)) == len(flat)          # no size asked twice
    assert set(flat) <= set(range(lo, hi + 1))
    assert len(flat) > (hi - lo + 1) - t["per_query"]
    assert gen.warmup_query(c, t) == blocks - 1   # the last, never reached
    other = [gen.query_batches(c, t, BIG_SEED + 1, i) for i in range(blocks)]
    assert other != q


def test_sweep_query_counts_its_deployments():
    cell = spec.cell("sweep_n64")
    drv = cell.kind().Driver(cell.config, cell.traffic, 5)
    pts = drv.points(gen.query_batches(cell.config, cell.traffic, 5, 0))
    per = cell.traffic["per_query"]
    assert len(pts) == 3 * per and drv.distinct(pts) == 3 * per
    assert {d for _a, _n, d, _net, _b in pts} == {5}


def test_arrivals_deterministic_and_distinct():
    a = gen.poisson_arrivals(BIG_SEED, 0, clients=64, requests_per_client=2,
                             rate=3.0)
    assert np.array_equal(a, gen.poisson_arrivals(
        BIG_SEED, 0, clients=64, requests_per_client=2, rate=3.0))
    b = gen.poisson_arrivals(BIG_SEED, 1, clients=64, requests_per_client=2,
                             rate=3.0)
    assert a.shape == (64, 2) and not np.array_equal(a, b)
    assert (np.diff(a, axis=1) > 0).all() and (a > 0).all()


@pytest.mark.parametrize("workload", ["clients_open_loop",
                                      "clients_mc_crash"])
def test_horizons_follow_the_rule(workload):
    cell = spec.cell(workload)
    assert gen.horizon_rounds(cell.config, cell.traffic) == \
        cell.traffic["rounds"]
    with pytest.raises(ValueError):
        gen.horizon_rounds(cell.config, dict(cell.traffic, rounds=1))


def test_crash_seeds_fit_32_bits_and_differ():
    seeds = {gen.small_seed(BIG_SEED, 4, i) for i in range(50)}
    assert len(seeds) == 50 and all(0 <= s < 2 ** 31 for s in seeds)


def test_smr_rate_matches_its_derivation():
    with open(spec.ROOT + "/bench/configs/smr_acp_n8_b64.json") as f:
        c = json.load(f)
    cap = c["batch_max"] / (2 * c["du"])
    assert c["rate"] == pytest.approx(c["util"] * cap / (c["clients"]
                                                         / c["n"]), rel=1e-15)
