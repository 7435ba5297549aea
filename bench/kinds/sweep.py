"""Sweep traffic: one ``repro.vecsim.sweep`` call per query.

A query is the grid slice at one cluster size ``n``: every algorithm of the
configuration at the size's G_R degree, on the configuration's network, and
``per_query`` batch sizes.  Every batch size of the configuration's range is
asked once per window, in an order permuted from the seed.

``correct``: every answer finite and positive, and a sample of deployments
drawn from the seed (one of each algorithm first, so each engine group is
compared in every run) equal to the plain reference's completion times,
median latency and throughput within the configuration's stated tolerance.
"""
from __future__ import annotations

import numpy as np

from bench.harness import traffic as gen
from bench.harness.compare import rel_gap

ANNOTATIONS = ("sweep",)
WORK_METRIC = "deployments_per_s"


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.n = int(traffic["n"])
        self.d = int(config["degree"][str(self.n)])
        self.window = tuple(config["summary_window"])

    def points(self, batches):
        """The query's deployments ``(algo, n, d, network, batch)``."""
        c = self.config
        return [(a, self.n, self.d, c["network"], b)
                for b in batches for a in c["algos"]]

    def distinct(self, points) -> int:
        """Distinct deployments: the G_U algorithms ignore the degree."""
        return len({(a, n, d if a == "allconcur" else None, net, b)
                    for a, n, d, net, b in points})

    def _sweep(self, batches):
        import jax

        from repro.vecsim import SweepConfig, sweep
        pts = self.points(batches)
        cfgs = [SweepConfig(algo=a, n=n, d=d, network=net, batch=b,
                            rounds=self.config["rounds"])
                for a, n, d, net, b in pts]
        with jax.profiler.TraceAnnotation("sweep"):
            res = sweep(cfgs, window=self.window)
        return (pts, np.asarray(res.median_latency),
                np.asarray(res.throughput),
                [np.asarray(c) for c in res.completion])

    def warm_up(self) -> None:
        self._sweep(gen.query_batches(
            self.config, self.traffic, self.seed,
            gen.warmup_query(self.config, self.traffic)))

    def answer(self, i: int):
        rec = self._sweep(gen.query_batches(self.config, self.traffic,
                                            self.seed, i))
        return self.distinct(rec[0]), rec

    def failed(self, record) -> bool:
        _pts, lat, thr, _compl = record
        return not bool(np.all(np.isfinite(lat) & (lat > 0)
                               & np.isfinite(thr) & (thr > 0)))

    def sample(self, records):
        """``(query, point)`` pairs to compare: one of each algorithm, then
        the rest of ``reference_sample`` drawn from the seed."""
        flat = [(q, j) for q, rec in enumerate(records)
                for j in range(len(rec[0]))]
        order = gen.stream(self.seed, 3).permutation(len(flat))
        picked, seen = [], set()
        for k in order:
            q, j = flat[k]
            algo = records[q][0][j][0]
            if algo not in seen:
                seen.add(algo)
                picked.append((q, j))
        for k in order:
            if len(picked) >= int(self.traffic["reference_sample"]):
                break
            if flat[k] not in picked:
                picked.append(flat[k])
        return picked

    def check(self, records):
        from bench.reference import rounds
        offsets = self.config["gr_offsets"][str(self.n)]
        gaps = {"completion_gap": 0.0, "latency_gap": 0.0,
                "throughput_gap": 0.0}
        picked = self.sample(records)
        for q, j in picked:
            pts, lat, thr, compl = records[q]
            a, n, _d, net, b = pts[j]
            ref_c, ref_lat, ref_thr = rounds.deployment(
                a, n, network=net, batch=b, rounds=self.config["rounds"],
                window=self.window, offsets=offsets)
            for k, got, ref in (("completion_gap", compl[j], ref_c),
                                ("latency_gap", lat[j], ref_lat),
                                ("throughput_gap", thr[j], ref_thr)):
                gaps[k] = max(gaps[k], rel_gap(got, ref))
        return gaps, f"{len(picked)} deployments vs the plain reference"
