"""Vectorized sweep bench: a ≥200-config grid (seeds × n × d × algorithm ×
network) through `repro.vecsim.sweep`, compared against pushing the same grid
through the event-driven `build_simulation`.

Default (CI) mode measures the event engine on a stratified subset and
extrapolates its grid cost (the whole point is that the full event grid takes
minutes); ``--full`` replays the entire grid through the event engine for an
exactly-measured ratio.  Emits the vec wall time, the event estimate and the
speedup; the driver's ``--json`` dump records the trajectory.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from repro.vecsim import grid, sweep

from .common import emit, run_sim

WINDOW = (3, 10)    # summary window of the sweep rows


def sweep_grid():
    """The 768-config grid: 3 algorithms x n x d x network x 16 seeds."""
    return grid(algo=("allconcur+", "allconcur", "allgather"),
                n=(8, 16, 32, 64), d=(2, 3), network=("sdc", "uniform"),
                seed=range(16), rounds=12)


def _run_event(cfg, window=WINDOW):
    met, _wall = run_sim(cfg.algo, cfg.n, batch=cfg.batch,
                         network=cfg.network, rounds=cfg.rounds,
                         max_time=60.0, d=cfg.resolved_d())
    return met.median_latency(), met.throughput(*window)


@dataclass(frozen=True)
class SMRShape:
    """Shape of the vectorized SMR client rows (one deployment at n=8)."""
    n: int
    batch: int
    util: float
    mode: str
    clients: int
    q: int              # requests per client, open loop
    du: float           # steady-state DUAL round period
    dr: float           # steady-state reliable (recovery) round latency
    rate: float         # per-client request rate
    rounds: int         # open-loop horizon
    schedules: int      # crash schedules
    mc_rounds: int      # crash-schedule horizon (one request per client)

    @property
    def cps(self) -> int:
        return self.clients // self.n


def smr_shape(full: bool) -> SMRShape:
    """>=1e5 open-loop clients per deployment (1e6 under ``full``) at
    ``util`` of the DUAL capacity; computes the round periods it is sized
    by (two short engine runs)."""
    from repro.vecsim.clients import smr_round_times

    n, batch, util, mode = 8, 64, 0.6, "allconcur+"
    clients = 1_000_000 if full else 100_000
    q = 2
    cps = clients // n
    e = np.asarray(smr_round_times(mode, n, reqs_per_round=batch,
                                   rounds=16).start)
    du = float(e[-1, 0] - e[-2, 0])          # steady round period, server 0
    r = smr_round_times("allconcur", n, reqs_per_round=batch, rounds=16)
    dr = float(np.asarray(r.completion)[-1, 0] - np.asarray(r.start)[-1, 0])
    # DUAL payloads ride two rounds (fresh + duplicate), so a server's
    # sustained capacity is batch/(2 du) req/s; run at `util` of it, with
    # enough rounds to drain the whole backlog plus slack
    cap = batch / (2 * du)
    rate = util * cap / cps
    # horizon: the per-client arrival span is Gamma(q, 1/rate) with mean
    # `base` rounds — cover 6x the mean so the unserved (censored) tail of
    # late arrivals is negligible (~1e-4 for q=2); one request per client
    # under crash schedules, over 8x its mean span
    base = int(cps * q / (util * batch / 2))
    mc_base = int(cps / (util * batch / 2))
    return SMRShape(n=n, batch=batch, util=util, mode=mode, clients=clients,
                    q=q, du=du, dr=dr, rate=rate, rounds=6 * base + 64,
                    schedules=256 if full else 64,
                    mc_rounds=8 * mc_base + 64)


def _smr_vec_rows(full: bool) -> None:
    """Vectorized SMR client rows: open-loop clients replayed against
    SMR-sized round timelines, plus a Monte-Carlo crash-schedule variant.
    Simulated time is deterministic, so the p50-based us_per_call sits in
    check_bench's strict band."""
    from repro.vecsim.clients import (arrival_times, client_latencies,
                                      mc_client_latencies, server_streams,
                                      smr_round_times)
    from repro.vecsim.failures import monte_carlo_times

    t0 = time.time()
    sh = smr_shape(full)
    times = smr_round_times(sh.mode, sh.n, reqs_per_round=sh.batch,
                            rounds=sh.rounds)
    s = server_streams(arrival_times(0, sh.clients, sh.q, sh.rate), sh.n)
    res = client_latencies(np.asarray(times.start).T,
                           np.asarray(times.completion).T, s,
                           mode=sh.mode, batch_max=sh.batch)
    wall = time.time() - t0
    p = res.percentiles
    emit("smr_vec_latency_n8", p[0.5] * 1e6,
         f"p50_ms={p[0.5]*1e3:.4f};p99_ms={p[0.99]*1e3:.4f};"
         f"p999_ms={p[0.999]*1e3:.4f};clients_simulated={sh.clients};"
         f"served={res.served};rounds={sh.rounds};wall_s={wall:.1f}")

    # ---- Monte-Carlo crash schedules: same population, one request each,
    # replayed against spliced (crash + recovery) timelines.  ~2 crashes per
    # schedule horizon: the pooled tail (p999) is shaped by detection +
    # recovery splices while p50 stays near failure-free
    t0 = time.time()
    mct = monte_carlo_times(sh.du, sh.dr, n=sh.n, batch=sh.batch,
                            mtbf=sh.mc_rounds * sh.du / 2,
                            rounds=sh.mc_rounds, n_schedules=sh.schedules,
                            seed=7)
    s_mc = server_streams(arrival_times(1, sh.clients, 1, sh.rate), sh.n)
    mc = mc_client_latencies(mct.entry, mct.deliver, s_mc, mode=sh.mode,
                             batch_max=sh.batch)
    wall = time.time() - t0
    mp = mc["percentiles"]
    emit("smr_vec_mc_crash_n8", mp[0.5] * 1e6,
         f"p50_ms={mp[0.5]*1e3:.4f};p99_ms={mp[0.99]*1e3:.4f};"
         f"p999_ms={mp[0.999]*1e3:.4f};clients_simulated={sh.clients};"
         f"served={mc['served']};schedules={sh.schedules};"
         f"rounds={sh.mc_rounds};wall_s={wall:.1f}")


def main(full: bool = False) -> None:
    cfgs = sweep_grid()
    window = WINDOW

    t0 = time.time()
    res = sweep(cfgs, window=window)
    cold = time.time() - t0
    t0 = time.time()
    res = sweep(cfgs, window=window)
    warm = time.time() - t0

    # event-engine cost for the same grid
    if full:
        t0 = time.time()
        for cfg in cfgs:
            _run_event(cfg, window)
        event_total = time.time() - t0
        event_label = "measured"
    else:
        # stratified subset: one config per (algo, n) cell, cost scaled by
        # the cell's population (network/d/seed barely change event cost)
        cells = {}
        for i, cfg in enumerate(cfgs):
            cells.setdefault((cfg.algo, cfg.n), []).append(i)
        event_total = 0.0
        for (algo, n), idxs in cells.items():
            t0 = time.time()
            _run_event(cfgs[idxs[0]], window)
            event_total += (time.time() - t0) * len(idxs)
        event_label = f"extrapolated_from_{len(cells)}"

    # vecsim recognizes that failure-free rounds are deterministic (seeds and
    # the unused G_U degree dedup away); the event engine replays every run
    from repro.vecsim.sweep import _dedup_key
    unique = len({_dedup_key(c) for c in cfgs})
    speedup = event_total / warm
    # wall_clock=1 tells scripts/check_bench.py that this row's us_per_call
    # is measured wall time (noisy run-to-run), not deterministic simulated
    # time like the smr_* rows — the regression gate applies its looser
    # wall-clock band to it
    emit("sweep_vec_grid", warm / len(cfgs) * 1e6,
         f"configs={len(cfgs)};unique_configs={unique};"
         f"vec_warm_s={warm:.3f};vec_cold_s={cold:.3f};"
         f"event_grid_s={event_total:.1f};speedup_x={speedup:.1f};"
         f"wall_clock=1;event_cost={event_label}")

    # sanity anchor: one row of actual sweep output per algorithm (n=16, sdc)
    for row in res.table():
        if row["n"] == 16 and row["network"] == "sdc" and row["seed"] == 0 \
                and row["d"] == 3:
            emit(f"sweep_vec_{row['algo']}_n16", row["median_latency_us"],
                 f"throughput_txn_s={row['throughput_txn_s']:.0f};"
                 f"round_period_us={row['round_period_us']:.3f}")

    _smr_vec_rows(full)


if __name__ == "__main__":
    main(full=False)
