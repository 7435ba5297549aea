"""Open-loop client traffic against one deployment's failure-free rounds.

An answer is the question a user asks of ``repro.vecsim``: the round
timeline of the deployment (``smr_round_times`` over the whole horizon),
then ``clients`` Poisson clients of ``requests_per_client`` requests each,
grouped per home server (``server_streams``) and assigned to rounds, acked
and pooled into p50/p99/p999 (``client_latencies``).  Each answer draws its
arrivals from ``(seed, answer)``.

``correct``: for a sample of answers drawn from the seed, the timeline
against the plain reference's (extended by its steady round period), and
every request's round and the percentiles against a numpy replay of the
client layer on the reference timeline (the served count follows from the
rounds).
"""
from __future__ import annotations

import numpy as np

from bench.harness import traffic as gen
from bench.harness.compare import rel_gap

ANNOTATIONS = ("smr_round_times", "arrival_times", "server_streams",
               "client_latencies")
WORK_METRIC = "client_reqs_per_s"
PCTS = (0.5, 0.99, 0.999)


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.rounds = gen.horizon_rounds(config, traffic)
        self.q = int(traffic["requests_per_client"])
        self.mode = config["algo"]

    def arrivals(self, i: int) -> np.ndarray:
        c = self.config
        return gen.poisson_arrivals(self.seed, i, clients=c["clients"],
                                    requests_per_client=self.q,
                                    rate=c["rate"])

    def _answer(self, i: int):
        import jax

        from repro.vecsim.clients import (client_latencies, server_streams,
                                          smr_round_times)
        c = self.config
        ta = jax.profiler.TraceAnnotation
        with ta("smr_round_times"):
            times = smr_round_times(self.mode, c["n"],
                                    reqs_per_round=c["batch_max"],
                                    rounds=self.rounds, network=c["network"],
                                    value_size=c["value_size"])
            start = np.asarray(times.start)
            compl = np.asarray(times.completion)
        with ta("arrival_times"):
            arr = self.arrivals(i)
        with ta("server_streams"):
            s = server_streams(arr, c["n"])
        with ta("client_latencies"):
            res = client_latencies(start.T, compl.T, s, mode=self.mode,
                                   batch_max=c["batch_max"], ps=PCTS)
        return (i, start, compl, np.asarray(res.round_idx),
                dict(res.percentiles), int(res.served))

    def warm_up(self) -> None:
        self._answer(gen.WARMUP)

    def answer(self, i: int):
        return self.config["clients"] * self.q, self._answer(i)

    def failed(self, record) -> bool:
        *_, pct, served = record
        return served <= 0 or not all(np.isfinite(v) and v > 0
                                      for v in pct.values())

    def check(self, records):
        from bench.reference import replay, rounds
        c = self.config
        n = c["n"]
        entry, compl, _period = rounds.periodic_timeline(
            self.mode, n, network=c["network"],
            nbytes=rounds.smr_bytes(c["batch_max"], c["value_size"]),
            rounds=self.rounds)
        delta = 2 if self.mode == "allconcur+" else 1
        lag = 1 if self.mode == "allconcur+" else 0
        want = min(int(self.traffic["reference_sample"]), len(records))
        picked = sorted(gen.stream(self.seed, 3).choice(
            len(records), size=want, replace=False))
        timeline = mismatch = pct_gap = 0.0
        for k in picked:
            i, start, cmp_, a0, pct, _served = records[k]
            timeline = max(timeline, rel_gap(start, entry),
                           rel_gap(cmp_, compl))
            s = replay.home_streams(self.arrivals(i), n)
            ref_a0 = replay.round_index(entry.T, s, delta=delta,
                                        batch_max=c["batch_max"])
            if a0.shape == ref_a0.shape:
                mismatch = max(mismatch, float(np.mean(a0 != ref_a0)))
            else:
                mismatch = 1.0
            lat = replay.latencies(ref_a0, compl.T, s, lag)
            ref_pct = replay.nearest_rank(lat, PCTS)
            pct_gap = max(pct_gap, rel_gap([pct[p] for p in PCTS],
                                           [ref_pct[p] for p in PCTS]))
        return ({"timeline_gap": timeline, "round_mismatch": mismatch,
                 "percentile_gap": pct_gap},
                f"{want} answers vs the plain reference's timeline and a "
                f"numpy replay")
