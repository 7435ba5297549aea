#!/usr/bin/env python3
"""Smoke run of the vecsim deployment path on one TPU chip.

Drives the path a user calls (``repro.vecsim.sweep``,
``clients.smr_round_times`` / ``client_latencies``,
``failures.monte_carlo_times`` / ``clients.mc_client_latencies`` and the two
Pallas kernels) at the sizes of ``benchmarks/sweep_vec.py --full``, and checks
every result against the references the tests hold it to: the event
simulator, an independent numpy recomputation on the host, or the kernels'
jnp references.  Each phase prints one line: its wall seconds, the compile
seconds inside them, what it compared and the largest difference.  The last
line is ``{"ok": true, "device": {...}}``; any failed check exits non-zero.

    python chip_smoke.py              # one chip, every phase
    python chip_smoke.py --chips 4    # the four-chip collectives phase only

Everything runs in this one process: a child that needs the chip would find
it held.  Without a TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import numpy as np  # noqa: E402

ALGOS = ("allconcur+", "allconcur", "allgather")
RTOL = 1e-12          # the event-simulator contract of tests/test_vecsim.py


class SmokeFailure(Exception):
    """A check of the smoke run did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def rel_diff(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-300)))


class CompileClock:
    """Sums JAX's compile-event durations (trace, lowering, backend compile),
    per program name, so each phase can report compile apart from run."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.total = 0.0
        self.backend = {}
        self._lock = threading.Lock()

    def __call__(self, event, duration, **kw):
        if event not in self.EVENTS:
            return
        with self._lock:
            self.total += duration
            if event == self.EVENTS[-1]:
                name = str(kw.get("fun_name", "?"))
                self.backend[name] = self.backend.get(name, 0.0) + duration


class Phase:
    """Times one phase and prints its line."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock
        self.fields = {}

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.clock.total
        return self

    def __exit__(self, exc_type, exc, tb):
        wall = time.perf_counter() - self.t0
        comp = self.clock.total - self.c0
        status = "ok" if exc_type is None else f"FAILED {exc_type.__name__}"
        line = {"phase": self.name, "status": status, "wall_s": wall,
                "compile_s": comp, **self.fields}
        if exc is not None:
            line["error"] = str(exc)[:2000]
        print(json.dumps(line, default=float), flush=True)
        return False


# ---------------------------------------------------------------- phases

def phase_device(need: int):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    print(json.dumps({"phase": "device", "platform": d0.platform,
                      "device_kind": d0.device_kind, "count": len(devs)}),
          flush=True)
    if d0.platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX's first device is {d0.platform}")
    if len(devs) < need:
        raise SmokeFailure(f"need {need} chips, JAX sees {len(devs)}")
    return devs


def phase_f64_probe(ph: Phase) -> None:
    """The engine's float64 operations on real deployment values, on the
    chip, bit for bit against the host: numpy where the result does not
    depend on evaluation order, and the same jitted program on the host CPU
    where it does (``cumsum``, and the associative scans the TPU engine uses
    for its prefix sums).  Reported, not gated: the contracts of later phases decide."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.compat import enable_x64
    from repro.vecsim.topology import unreliable_tables

    t = unreliable_tables(8, network="sdc")
    x = np.concatenate([t.prop.ravel(), t.send_off.ravel(), t.occ.ravel()])
    y = np.roll(x[::-1], 7) * 1.5 + 3e-7          # distinct real-sized times
    r = (np.arange(x.size) % 12).astype(np.float64)
    ops = {
        "add": (lambda a, b, c: a + b, x + y),
        "subtract": (lambda a, b, c: a - b, x - y),
        "multiply": (lambda a, b, c: a * b, x * y),
        "divide": (lambda a, b, c: a / b, x / y),
        "min": (lambda a, b, c: jnp.minimum(a, b), np.minimum(x, y)),
        "max": (lambda a, b, c: jnp.maximum(a, b), np.maximum(x, y)),
        "compare": (lambda a, b, c: jnp.stack([a < b, a <= b, a == b]),
                    np.stack([x < y, x <= y, x == y])),
        "lexsort": (lambda a, b, c: jnp.lexsort((b, c)), np.lexsort((y, r))),
        "cumsum": (lambda a, b, c: jnp.cumsum(a), None),
        "cummax": (lambda a, b, c: jax.lax.cummax(b - a, axis=0),
                   np.maximum.accumulate(y - x)),
        "cumsum_assoc_scan": (
            lambda a, b, c: jax.lax.associative_scan(jnp.add, a), None),
        "cummax_assoc_scan": (
            lambda a, b, c: jax.lax.associative_scan(jnp.maximum, b - a),
            None),
    }
    try:
        cpu = jax.devices("cpu")[0]
    except RuntimeError:              # JAX_PLATFORMS left the CPU out
        cpu = None
    table = {}
    with enable_x64():
        for name, (fn, expect) in ops.items():
            got = np.asarray(jax.jit(fn)(x, y, r))
            if expect is None:
                if cpu is None:
                    table[name] = {"exact": None, "max_rel_diff": None}
                    continue
                expect = np.asarray(
                    jax.jit(fn)(*jax.device_put((x, y, r), cpu)))
            exact = bool(np.array_equal(got, expect))
            if exact or got.dtype == bool:
                diff = 0.0 if exact else float(np.mean(got != expect))
            elif name == "lexsort":
                diff = float(np.mean(got != expect))
            else:
                diff = rel_diff(got, expect)
            table[name] = {"exact": exact, "max_rel_diff": diff}
    ph.fields.update(values=int(x.size), ops=table,
                     exact=[k for k, v in table.items() if v["exact"]],
                     inexact=[k for k, v in table.items()
                              if v["exact"] is False])


def phase_sweep(ph: Phase) -> None:
    """The 768-config sweep grid, cold then warm, against the event
    simulator on one sdc config per (algo, n in {8, 16}) at rtol 1e-12."""
    from benchmarks.common import run_sim
    from benchmarks.sweep_vec import WINDOW, sweep_grid
    from repro.vecsim import sweep

    cfgs = sweep_grid()
    t0 = time.perf_counter()
    c0 = ph.clock.total
    sweep(cfgs, window=WINDOW, engine="vec")
    cold, cold_compile = time.perf_counter() - t0, ph.clock.total - c0
    t0 = time.perf_counter()
    res = sweep(cfgs, window=WINDOW, engine="vec")
    warm = time.perf_counter() - t0
    check(np.isfinite(res.median_latency).all()
          and np.isfinite(res.throughput).all(), "non-finite sweep result")

    worst = 0.0
    compared = []
    for algo in ALGOS:
        for n in (8, 16):
            i = next(j for j, c in enumerate(cfgs)
                     if (c.algo, c.n, c.network, c.seed, c.d)
                     == (algo, n, "sdc", 0, 3))
            cfg = cfgs[i]
            met, _wall = run_sim(algo, n, batch=cfg.batch, network="sdc",
                                 rounds=cfg.rounds, d=cfg.resolved_d())
            lat = rel_diff(res.median_latency[i], met.median_latency())
            thr = rel_diff(res.throughput[i], met.throughput(*WINDOW))
            worst = max(worst, lat, thr)
            compared.append(f"{algo}/n{n}")
            check(lat <= RTOL and thr <= RTOL,
                  f"sweep {algo} n={n}: median latency rel diff {lat:.3e}, "
                  f"throughput rel diff {thr:.3e} > {RTOL}")
    ph.fields.update(
        configs=len(cfgs), cold_s=cold, cold_compile_s=cold_compile,
        warm_s=warm,
        compile_by_program={k: v for k, v in sorted(ph.clock.backend.items())
                            if k.startswith("jit(vecsim_")},
        compared=f"median latency and throughput vs event sim "
                 f"({', '.join(compared)}), rtol {RTOL}",
        max_rel_diff=worst)


def _host_round_index(entry, submits, *, delta, batch_max):
    """Independent numpy recomputation of the client pipeline's integer part:
    arrivals per round entry, the capacity recurrence, each request's round.
    ``entry[..., K]`` round entries against ascending ``submits[..., M]``,
    leading axes broadcast; returns ``[..., M]`` int32."""
    lead = np.broadcast_shapes(entry.shape[:-1], submits.shape[:-1])
    entry = np.broadcast_to(entry, lead + entry.shape[-1:])
    submits = np.broadcast_to(submits, lead + submits.shape[-1:])
    rows, k = list(np.ndindex(lead)), entry.shape[-1]
    counts = np.empty((k, len(rows)), np.int32)
    for b, i in enumerate(rows):
        counts[:, b] = np.searchsorted(submits[i], entry[i], side="right")
    cum = np.zeros((k, len(rows)), np.int32)
    for r in range(k):
        back = cum[r - delta] if r >= delta else 0
        cum[r] = np.minimum(counts[r], back + batch_max)
    del counts
    cum = np.ascontiguousarray(cum.T)
    ranks = np.arange(1, submits.shape[-1] + 1)
    out = np.empty((len(rows), ranks.size), np.int32)
    for b in range(len(rows)):
        out[b] = np.searchsorted(cum[b], ranks, side="left")
    return out.reshape(lead + (ranks.size,))


def _sorted_nearest_rank(lat, ps) -> dict:
    lat.sort()
    return {p: float(lat[min(int(p * lat.size), lat.size - 1)]) for p in ps}


def phase_clients(ph: Phase, sh) -> None:
    """Open-loop clients at the --full shape, plus the event-simulator ack
    check (bit for bit) and the host recomputation of round indices."""
    from repro.vecsim.clients import (arrival_times, client_latencies,
                                      server_streams, smr_round_times)
    from repro.vecsim.eventref import (event_timelines, run_open_loop,
                                       server_fifo)

    # reference 1: event-simulator acks from event-extracted timelines
    acks_checked = 0
    for algo in ALGOS:
        entries, compl, subs, acks = run_open_loop(algo, 8)
        e, c = event_timelines(entries, compl, 8)
        by_server, s = server_fifo(subs, acks, 8)
        res = client_latencies(e, c, s, mode=algo, batch_max=2)
        for h in range(8):
            for j, u in enumerate(by_server[h]):
                if u in acks and res.valid[h, j]:
                    check(res.ack[h, j] == acks[u]
                          and res.latency[h, j] == acks[u] - subs[u],
                          f"{algo} ack of {u} differs from the event sim")
                    acks_checked += 1
    check(acks_checked >= 3 * 8 * 12, f"only {acks_checked} acks compared")

    # the 10^6-client run of benchmarks/sweep_vec.py --full
    times = smr_round_times(sh.mode, sh.n, reqs_per_round=sh.batch,
                            rounds=sh.rounds)
    s = server_streams(arrival_times(0, sh.clients, sh.q, sh.rate), sh.n)
    entry = np.asarray(times.start).T
    res = client_latencies(entry, np.asarray(times.completion).T, s,
                           mode=sh.mode, batch_max=sh.batch)

    # reference 2: round index of every request (the part the chip computes)
    # and the served count
    a0 = _host_round_index(entry, s, delta=2, batch_max=sh.batch)
    served = int(((a0 + 1 < sh.rounds) & np.isfinite(s)).sum())
    mismatched = int((a0 != res.round_idx).sum())
    check(mismatched == 0, f"{mismatched} round indices differ from numpy")
    check(served == res.served, f"served {res.served} vs numpy {served}")
    pct = res.percentiles
    check(all(np.isfinite(v) and v > 0 for v in pct.values()),
          f"bad percentiles {pct}")
    ph.fields.update(
        clients=sh.clients, requests=int(s.size), rounds=sh.rounds,
        served=res.served, p50_ms=pct[0.5] * 1e3, p99_ms=pct[0.99] * 1e3,
        p999_ms=pct[0.999] * 1e3,
        compared=f"{acks_checked} event-sim acks bit for bit; "
                 f"{int(s.size)} round indices (computed on the chip) and "
                 f"the served count vs numpy exactly",
        max_rel_diff=0.0, mismatched_round_idx=mismatched)


def phase_crash(ph: Phase, sh) -> None:
    """Crash schedules at the --full shape.  The failure-free limit must give
    the total time of ``rounds`` round periods; the pooled client run must
    equal a numpy replay of every schedule on the host: served count and
    percentiles, exactly."""
    from repro.vecsim.clients import (arrival_times, mc_client_latencies,
                                      server_streams)
    from repro.vecsim.failures import monte_carlo_times

    mc_rounds = sh.mc_rounds
    ff = monte_carlo_times(sh.du, sh.dr, n=sh.n, batch=sh.batch, mtbf=1e12,
                           rounds=mc_rounds, n_schedules=sh.schedules, seed=7)
    check(int(ff.crashes.max()) == 0, "a crash landed with mtbf=1e12")
    ff_diff = rel_diff(ff.total_time, mc_rounds * sh.du)
    check(ff_diff <= RTOL, f"failure-free total time vs {mc_rounds} x du: "
                           f"rel diff {ff_diff:.3e} > {RTOL}")
    plain = 0.0                 # the uncompensated float64 clock, for scale
    for _ in range(mc_rounds):
        plain += sh.du

    mct = monte_carlo_times(sh.du, sh.dr, n=sh.n, batch=sh.batch,
                            mtbf=mc_rounds * sh.du / 2, rounds=mc_rounds,
                            n_schedules=sh.schedules, seed=7)
    s = server_streams(arrival_times(1, sh.clients, 1, sh.rate), sh.n)
    t0 = time.perf_counter()
    mc = mc_client_latencies(mct.entry, mct.deliver, s, mode=sh.mode,
                             batch_max=sh.batch)
    pipeline_s = time.perf_counter() - t0
    pct = mc["percentiles"]
    check(mc["served"] > 0, "no request served under crash schedules")
    check(all(np.isfinite(v) for v in pct.values()),
          f"non-finite percentiles {pct}")

    # reference: every schedule replayed in numpy (rows = schedule x server)
    t0 = time.perf_counter()
    S = sh.schedules
    a0 = _host_round_index(mct.entry[:, None, :], s[None], delta=2,
                           batch_max=sh.batch)                # [S, n, M]
    valid = (a0 < mc_rounds) & np.isfinite(s)
    lat = np.take_along_axis(mct.deliver[:, None, :],
                             np.minimum(a0, mc_rounds - 1), axis=-1) - s
    host = lat[valid]
    del a0, valid, lat
    host_pct = _sorted_nearest_rank(host, pct)
    check(host.size == mc["served"],
          f"served {mc['served']} vs numpy {host.size}")
    for p, v in pct.items():
        check(v == host_pct[p], f"pooled p{p} {v!r} vs numpy {host_pct[p]!r}")
    ph.fields.update(
        schedules=S, rounds=mc_rounds, clients=sh.clients,
        crashes_mean=float(mct.crashes.mean()), served=mc["served"],
        p50_ms=pct[0.5] * 1e3, p99_ms=pct[0.99] * 1e3,
        p999_ms=pct[0.999] * 1e3, client_pipeline_s=pipeline_s,
        host_reference_s=time.perf_counter() - t0,
        compared=f"failure-free total time vs rounds x du, rtol {RTOL}; "
                 f"served count and pooled percentiles vs a numpy replay of "
                 f"all {S} schedules, exactly",
        max_rel_diff=ff_diff,
        plain_float64_sum_rel_diff=rel_diff(plain, mc_rounds * sh.du))


def phase_kernels(ph: Phase, sh) -> None:
    """Both Pallas kernels compiled (interpret=False) in float32 at the
    engine's real widths, bit for bit against their jnp references."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.clients_segred import (segment_counts,
                                              segment_counts_reference)
    from repro.kernels.tropical import (tropical_matmul,
                                        tropical_matmul_threshold)
    from repro.vecsim.topology import reliable_tables

    rng = np.random.default_rng(0)
    n, K, B = 64, 12, 16
    t = reliable_tables(n, d=3, network="sdc")
    dense = np.where(t.adj, t.edge_off + t.prop, np.inf)
    b = jnp.asarray(np.concatenate([dense, dense]), jnp.float32)  # (128, 64)
    a = jnp.asarray(rng.uniform(0, 1e-3, (K * B, n, 2 * n)), jnp.float32)
    thr = jnp.asarray(rng.uniform(0, 2e-3, (K * B, n, n)), jnp.float32)
    big = 1e12

    @jax.jit
    def ref_minplus(a, b, thr):
        cand = a[..., :, :, None] + b[None, :, :]
        return (jnp.min(cand, axis=-2),
                jnp.min(jnp.where(cand >= thr[..., None, :], cand, big),
                        axis=-2))

    mm = jax.jit(lambda a, b: tropical_matmul(a, b, interpret=False))
    mt = jax.jit(lambda a, b, t: tropical_matmul_threshold(
        a, b, t, big=big, interpret=False))
    plain_ref, gated_ref = ref_minplus(a, b, thr)
    plain = mm(a, b)
    plain_t, gated = mt(a, b, thr)
    check(bool(jnp.array_equal(plain, plain_ref)), "tropical_matmul differs")
    check(bool(jnp.array_equal(plain_t, plain_ref))
          and bool(jnp.array_equal(gated, gated_ref)),
          "tropical_matmul_threshold differs")

    m_per, k_edges = sh.cps, 4096
    s = jnp.asarray(np.sort(rng.exponential(1.0, (sh.n, m_per)), axis=1),
                    jnp.float32)
    edges = jnp.asarray(np.sort(rng.uniform(0, 8.0, (sh.n, k_edges)),
                                axis=1), jnp.float32)
    sc = jax.jit(lambda s, e: segment_counts(s, e, interpret=False))
    counts = sc(s, edges)
    counts_ref = jax.jit(segment_counts_reference)(s, edges)
    check(bool(jnp.array_equal(counts, counts_ref)), "segment_counts differs")

    def timed(fn, *args, reps=5):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps

    ph.fields.update(
        tropical_shape=f"{tuple(a.shape)}x{tuple(b.shape)}",
        segment_counts_shape=f"{tuple(s.shape)}x{tuple(edges.shape)}",
        warm_s={"tropical_matmul": timed(mm, a, b),
                "tropical_matmul_threshold": timed(mt, a, b, thr),
                "segment_counts": timed(sc, s, edges)},
        compared="tropical_matmul, tropical_matmul_threshold and "
                 "segment_counts vs jnp references on the chip, bit for bit",
        max_abs_diff=0.0)


def phase_collectives(ph: Phase, devs) -> None:
    """G_U/G_R all-gathers and all-reduces over a 4-chip mesh against
    jax.lax.all_gather / psum in the same program, exactly."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.collectives.ops import (doubling_allgather, graph_allreduce,
                                       gs_flood_allgather, ring_allgather)
    from repro.kernels.compat import shard_map

    devs = devs[:4]
    mesh = Mesh(np.array(devs), ("x",))
    rows = 1024
    # small integers: every sum is exact in float32, so all orders agree
    x = jnp.asarray(np.arange(4 * rows * 256) % 1000, jnp.float32).reshape(
        4, rows, 256)
    x = jax.device_put(x, NamedSharding(mesh, P("x")))
    shard_devs = {s.device for s in x.addressable_shards}
    check(shard_devs == set(devs), f"input shards on {shard_devs}")

    def run(body):
        fn = jax.jit(shard_map(lambda a: body(a[0])[None], mesh=mesh,
                               in_specs=P("x"), out_specs=P("x")))
        out = fn(x)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        warm = time.perf_counter() - t0
        on = {s.device for s in out.addressable_shards}
        check(on == set(devs), f"output shards on {on}")
        return np.asarray(out), warm

    gathered_ref, _ = run(lambda a: jax.lax.all_gather(a, "x"))
    reduced_ref, _ = run(lambda a: jax.lax.psum(a, "x"))
    results, warm = {}, {}
    for name, body, ref in [
            ("ring_allgather", lambda a: ring_allgather(a, "x"),
             gathered_ref),
            ("doubling_allgather", lambda a: doubling_allgather(a, "x"),
             gathered_ref),
            ("gs_flood_allgather_d3",
             lambda a: gs_flood_allgather(a, "x", d=3), gathered_ref),
            ("allreduce_binomial",
             lambda a: graph_allreduce(a, "x", strategy="binomial"),
             reduced_ref),
            ("allreduce_gs_flood",
             lambda a: graph_allreduce(a, "x", strategy="gs_flood", d=3),
             reduced_ref)]:
        out, warm[name] = run(body)
        results[name] = bool(np.array_equal(out, ref))
        check(results[name], f"{name} differs from the XLA collective")
    ph.fields.update(
        devices=[str(d) for d in devs], shard_shape=[rows, 256],
        exact=results, warm_s=warm,
        compared="ring/doubling/gs_flood(d=3) all-gathers vs "
                 "lax.all_gather; binomial/gs_flood all-reduce vs psum",
        max_abs_diff=0.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip collectives phase")
    args = ap.parse_args(argv)

    import jax

    from benchmarks.common import set_compile_cache
    from benchmarks.sweep_vec import smr_shape

    set_compile_cache()
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)

    devs = phase_device(4 if args.chips == 4 else 1)
    if args.chips == 4:
        with Phase("collectives_4chip", clock) as ph:
            phase_collectives(ph, devs)
    else:
        with Phase("f64_probe", clock) as ph:
            phase_f64_probe(ph)
        with Phase("sweep", clock) as ph:
            phase_sweep(ph)
        with Phase("clients_open_loop", clock) as ph:
            sh = smr_shape(full=True)
            phase_clients(ph, sh)
        with Phase("crash_schedules", clock) as ph:
            phase_crash(ph, sh)
        with Phase("kernels", clock) as ph:
            phase_kernels(ph, sh)
    d0 = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
