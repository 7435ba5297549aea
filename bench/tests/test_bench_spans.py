"""The program's own host spans (``vecsim.*``), read back from a real
profiler trace through ``trace.extract``: their names, how often each
appears, and that tracing leaves every result as it was."""
from collections import Counter

import numpy as np

from bench.harness import trace

SWEEP_SPANS = {"vecsim.tables", "vecsim.dispatch", "vecsim.sync"}
CLIENT_SPANS = {"vecsim.dispatch", "vecsim.server_streams",
                "vecsim.order_keys", "vecsim.gather", "vecsim.percentiles"}
SPANS = SWEEP_SPANS | CLIENT_SPANS | {"vecsim.resolve"}


def _traced(tmp_path, fn):
    """``fn()`` under the profiler as ``bench/run.py --trace 1`` runs it;
    returns its result and the count of each program span."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    events = trace.extract(trace.find_xplane(str(tmp_path)), SPANS)
    return out, Counter(n for n, _s, _d in events["host"]
                        if n != trace.ANSWER)


def _sweep():
    from repro.vecsim import grid, sweep
    res = sweep(grid(algo=("allconcur+", "allconcur"), n=(8,), d=(3,),
                     batch=(1, 4)))
    return (res.median_latency, res.throughput, res.completion)


def _clients():
    from repro.vecsim.clients import (client_latencies, mc_client_latencies,
                                      server_streams)
    from repro.vecsim.failures import monte_carlo_times
    n, k = 8, 64
    rng = np.random.default_rng(11)
    arrivals = np.cumsum(rng.exponential(1e-4, (256, 2)), axis=1)
    s = server_streams(arrivals, n)
    entry = np.tile(np.arange(k) * 1.2e-4, (n, 1))
    one = client_latencies(entry, entry + 1.2e-4, s, mode="allconcur+",
                           batch_max=16)
    mct = monte_carlo_times(120e-6, 180e-6, n=n, batch=16, mtbf=4e-3,
                            rounds=k, n_schedules=4, seed=3)
    mc = mc_client_latencies(mct.entry, mct.deliver, s, mode="allconcur+",
                             batch_max=16)
    return (one.round_idx, one.latency, one.percentiles, mct.entry,
            mct.deliver, mc)


def _same(a, b):
    if isinstance(a, (tuple, list)):
        return len(a) == len(b) and all(_same(x, y) for x, y in zip(a, b))
    if isinstance(a, dict):
        return a.keys() == b.keys() and all(_same(a[k], b[k]) for k in a)
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and \
        a.tobytes() == b.tobytes()


def test_sweep_spans(tmp_path):
    plain = _sweep()
    traced, spans = _traced(tmp_path, _sweep)
    assert _same(plain, traced)
    assert set(spans) == SWEEP_SPANS             # no vecsim.resolve
    # two engine groups (G_U, G_R at d = 3), one of them run_reliable
    assert spans["vecsim.tables"] == 2 + 1
    assert spans["vecsim.dispatch"] == spans["vecsim.sync"] == 2


def test_client_spans(tmp_path):
    plain = _clients()
    traced, spans = _traced(tmp_path, _clients)
    assert _same(plain, traced)
    assert set(spans) == CLIENT_SPANS
    # the splice and the pipeline twice; arrivals are grouped once
    assert spans["vecsim.dispatch"] == 3
    assert spans["vecsim.server_streams"] == 1
    assert spans["vecsim.order_keys"] == spans["vecsim.gather"] \
        == spans["vecsim.percentiles"] == 2


def test_unresolved_warm_solve_counts_one_resolve(tmp_path):
    from repro.vecsim import engine, reliable_tables
    t = reliable_tables(8, d=3, network="sdc", batch=4)

    def capped():
        return engine.run_reliable(t.adj, t.edge_off, t.occ, t.prop,
                                   rounds=12, max_iters=1)

    def fields(rt):
        return rt.completion, rt.start, rt.iterations

    traced, spans = _traced(tmp_path, capped)
    assert spans["vecsim.resolve"] == 1
    assert spans["vecsim.dispatch"] == 2         # warm, then cold
    assert spans["vecsim.tables"] == spans["vecsim.sync"] == 1
    assert _same(fields(traced), fields(capped()))

