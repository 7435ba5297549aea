"""Event-simulator references for the vectorized client layer.

Runs a small open-loop SMR deployment through the event simulator
(:func:`repro.sim.runner.build_smr_simulation`) and records what the
vectorized pipeline consumes and produces, at the simulator's own floats:
per-server round entry/completion timelines, per-request submit times in
each home server's FIFO order, and the ack time of every request.  Given the
timelines, :func:`repro.vecsim.clients.client_latencies` must reproduce the
acks bit for bit; the tests and ``chip_smoke.py`` both hold it to that.
"""
from __future__ import annotations

import numpy as np

from ..sim.runner import build_smr_simulation
from ..smr.workload import WorkloadConfig


def instrument(sim, smr, n, dual):
    """Record per-server round timelines and per-uid submit/ack times at the
    simulator's own floats.  ``payload_for`` / ``on_deliver_cb`` are plain
    instance attributes on the servers, so the harness-installed callbacks
    can be wrapped after ``build_smr_simulation`` returns."""
    entries = {h: {} for h in range(n)}
    compl = {h: {} for h in range(n)}
    for h in range(n):
        srv = sim.servers[h]
        orig_pf = srv.payload_for

        def pf(rnd, _h=h, _o=orig_pf):
            entries[_h][rnd] = sim.now
            return _o(rnd)

        srv.payload_for = pf
        orig_cb = srv.on_deliver_cb

        def cb(rec, _h=h, _o=orig_cb):
            # DUAL A-delivers round r at the completion of round r+1
            compl[_h][rec.round + 1 if dual else rec.round] = sim.now
            if _o:
                _o(rec)

        srv.on_deliver_cb = cb
    subs, acks = {}, {}
    o_sub, o_ack = smr.on_submit, smr.on_ack

    def on_submit(uid, t):
        subs.setdefault(uid, t)
        o_sub(uid, t)

    def on_ack(uid, t, is_read):
        if uid not in acks:
            acks[uid] = t
        o_ack(uid, t, is_read)

    smr.on_submit, smr.on_ack = on_submit, on_ack
    return entries, compl, subs, acks


def event_timelines(entries, compl, n):
    """Dense [n, K] entry/completion arrays (E[h, k] = entry of round k+1).
    completion(r) == entry(r+1) is the same simulator event, so the shared
    rounds reuse the identical float."""
    k = min(max(entries[h]) for h in range(n))
    e = np.full((n, k), np.inf)
    c = np.full((n, k), np.inf)
    for h in range(n):
        for r in range(1, k + 1):
            e[h, r - 1] = entries[h][r]
        for r, t in compl[h].items():
            if r <= k:
                c[h, r - 1] = t
        c[h, :k - 1] = e[h, 1:]
    return e, c


def server_fifo(subs, acks, n):
    """Per-server FIFO uid order + padded [n, M] submit-time streams."""
    by_server = {h: sorted((u for u in subs if u[0] % n == h),
                           key=lambda u: (subs[u], u[0]))
                 for h in range(n)}
    m = max(len(us) for us in by_server.values())
    s = np.full((n, m), np.inf)
    for h, us in by_server.items():
        s[h, :len(us)] = [subs[u] for u in us]
    return by_server, s


def run_open_loop(algo, n, *, cps=2, rpc=6, batch_max=2, rate=3000.0):
    cfg = WorkloadConfig(read_ratio=0.0, distribution="uniform", nkeys=64,
                         num_clients=cps * n, value_size=16,
                         linearizable_reads=True, arrival="open",
                         open_rate=rate, seed=0)
    sim, smr, _services = build_smr_simulation(
        algo, n, workload=cfg, requests_per_client=rpc,
        batch_max=batch_max, network="sdc")
    rec = instrument(sim, smr, n, algo == "allconcur+")
    gen = sim.workload
    sim.start()
    sim.run(until=lambda: all(c.acked >= rpc for c in gen.clients),
            max_time=60.0)
    if not all(c.acked >= rpc for c in gen.clients):
        raise RuntimeError(f"{algo} n={n}: open-loop clients not all acked "
                           f"within the simulated time limit")
    return rec
