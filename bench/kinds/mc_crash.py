"""Open-loop clients under sampled crash schedules.

An answer: ``schedules`` crash schedules of the deployment spliced over the
horizon (``repro.vecsim.failures.monte_carlo_times``, crashes at a mean gap
of ``mtbf_horizons`` horizons, seeded from ``(seed, answer)``), then one
request from each of ``clients`` Poisson clients replayed against every
schedule and pooled into p50/p99/p999 (``mc_client_latencies``).

``correct``: for a sample of answers drawn from the seed, the spliced
timelines against a plain splice of the same crash times on the host, and
the served count and pooled percentiles against a numpy replay of the
client layer on those reference timelines.
"""
from __future__ import annotations

import numpy as np

from bench.harness import traffic as gen
from bench.harness.compare import rel_gap

ANNOTATIONS = ("monte_carlo_times", "arrival_times", "server_streams",
               "mc_client_latencies")
WORK_METRIC = "client_reqs_per_s"
PCTS = (0.5, 0.99, 0.999)
MAX_FAILURES = 4        # monte_carlo_times' default: f <= d - 1 at n = 8


def crash_times(seed: int, *, schedules: int, mtbf: float) -> np.ndarray:
    """The crash times ``monte_carlo_times(seed=seed)`` samples: i.i.d.
    exponential gaps of mean ``mtbf`` (JAX's threefry), summed, computed on
    the host's CPU in float64."""
    import jax
    import jax.numpy as jnp
    with jax.default_device(jax.devices("cpu")[0]), jax.enable_x64(True):
        gaps = jax.random.exponential(jax.random.PRNGKey(seed),
                                      (schedules, MAX_FAILURES),
                                      dtype=jnp.float64) * mtbf
        return np.asarray(jnp.cumsum(gaps, axis=1))


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.rounds = gen.horizon_rounds(config, traffic)
        self.q = int(traffic["requests_per_client"])
        self.schedules = int(traffic["schedules"])
        self.mode = config["algo"]
        self.mtbf = self.rounds * config["du"] * traffic["mtbf_horizons"]

    def arrivals(self, i: int) -> np.ndarray:
        c = self.config
        return gen.poisson_arrivals(self.seed, i, clients=c["clients"],
                                    requests_per_client=self.q,
                                    rate=c["rate"])

    def crash_seed(self, i: int) -> int:
        return gen.small_seed(self.seed, 4, i)

    def _answer(self, i: int):
        import jax

        from repro.vecsim.clients import mc_client_latencies, server_streams
        from repro.vecsim.failures import monte_carlo_times
        c = self.config
        ta = jax.profiler.TraceAnnotation
        with ta("monte_carlo_times"):
            mct = monte_carlo_times(
                c["du"], c["dr"], n=c["n"], batch=c["batch_max"],
                mtbf=self.mtbf, fd_timeout=c["fd_timeout"],
                rounds=self.rounds, n_schedules=self.schedules,
                seed=self.crash_seed(i), max_failures=MAX_FAILURES)
        with ta("arrival_times"):
            arr = self.arrivals(i)
        with ta("server_streams"):
            s = server_streams(arr, c["n"])
        with ta("mc_client_latencies"):
            mc = mc_client_latencies(mct.entry, mct.deliver, s,
                                     mode=self.mode,
                                     batch_max=c["batch_max"], ps=PCTS)
        return (i, np.asarray(mct.entry), np.asarray(mct.deliver),
                dict(mc["percentiles"]), int(mc["served"]))

    def warm_up(self) -> None:
        self._answer(gen.WARMUP)

    def answer(self, i: int):
        return (self.config["clients"] * self.q * self.schedules,
                self._answer(i))

    def failed(self, record) -> bool:
        *_, pct, served = record
        return served <= 0 or not all(np.isfinite(v) and v > 0
                                      for v in pct.values())

    def check(self, records):
        from bench.reference import replay
        c = self.config
        n = c["n"]
        delta = 2 if self.mode == "allconcur+" else 1
        want = min(int(self.traffic["reference_sample"]), len(records))
        picked = sorted(gen.stream(self.seed, 3).choice(
            len(records), size=want, replace=False))
        timeline = served_gap = pct_gap = 0.0
        for k in picked:
            i, entry, deliver, pct, served = records[k]
            ct = crash_times(self.crash_seed(i), schedules=self.schedules,
                             mtbf=self.mtbf)
            ref_entry, ref_deliver, _crashes = replay.splice(
                c["du"], c["dr"], ct, rounds=self.rounds,
                fd_timeout=c["fd_timeout"])
            timeline = max(timeline, rel_gap(entry, ref_entry),
                           rel_gap(deliver, ref_deliver))
            s = replay.home_streams(self.arrivals(i), n)
            a0 = replay.round_index(ref_entry[:, None, :], s[None],
                                    delta=delta, batch_max=c["batch_max"])
            lat = replay.latencies(a0, ref_deliver[:, None, :], s[None], 0)
            del a0
            ref_pct = replay.nearest_rank(lat, PCTS)
            served_gap = max(served_gap, abs(served - lat.size) / lat.size)
            pct_gap = max(pct_gap, rel_gap([pct[p] for p in PCTS],
                                           [ref_pct[p] for p in PCTS]))
        return ({"timeline_gap": timeline, "served_gap": served_gap,
                 "percentile_gap": pct_gap},
                f"{want} answers vs a host splice of the same crash times "
                f"and a numpy replay")
