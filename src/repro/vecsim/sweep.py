"""User-facing multi-deployment sweep API.

``sweep(configs)`` evaluates a list of independent deployments (seeds x n x d
x network x batch x algorithm) in a handful of vmapped engine calls instead
of thousands of per-event heap operations.  Configs are grouped by batchable
signature (engine kind, n, d, rounds); each group is stacked into dense
arrays and relaxed in one jit-compiled program.

Example::

    from repro.vecsim import SweepConfig, grid, sweep
    res = sweep(grid(algo=("allconcur+", "allgather"), n=(8, 16, 32),
                     seed=range(4)))
    print(res.table()[:3])
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from ..core.digraph import resilience_degree
from . import engine as _engine
from . import topology
from .spans import span

UNRELIABLE_MODES = ("allconcur+", "allgather")


@dataclass(frozen=True)
class SweepConfig:
    """One deployment point.  ``seed`` only matters for failure sampling
    (failure-free rounds are deterministic); it is kept in the grid so
    Monte-Carlo studies and result tables stay aligned with event-sim runs."""
    algo: str = "allconcur+"      # allconcur+ | allconcur | allgather
    n: int = 16
    d: Optional[int] = None       # G_R degree (allconcur); None -> resilience_degree
    network: str = "sdc"          # uniform | sdc | mdc
    batch: int = 4
    rounds: int = 12
    seed: int = 0

    def resolved_d(self) -> int:
        return self.d if self.d is not None else resilience_degree(self.n)

    def engine_kind(self) -> str:
        return "reliable" if self.algo == "allconcur" else "unreliable"


@dataclass
class SweepResult:
    configs: List[SweepConfig]
    median_latency: np.ndarray    # [C] seconds
    throughput: np.ndarray        # [C] txn / s / server
    round_period: np.ndarray      # [C] seconds, steady-state round length
    completion: List[np.ndarray]  # per config: [rounds, n] completion times

    def table(self) -> List[Dict]:
        rows = []
        for i, cfg in enumerate(self.configs):
            rows.append({
                "algo": cfg.algo, "n": cfg.n, "d": cfg.resolved_d(),
                "network": cfg.network, "batch": cfg.batch, "seed": cfg.seed,
                "median_latency_us": float(self.median_latency[i]) * 1e6,
                "throughput_txn_s": float(self.throughput[i]),
                "round_period_us": float(self.round_period[i]) * 1e6,
            })
        return rows


def grid(*, algo: Sequence[str] = ("allconcur+",), n: Sequence[int] = (16,),
         d: Sequence[Optional[int]] = (None,),
         network: Sequence[str] = ("sdc",), batch: Sequence[int] = (4,),
         rounds: int = 12, seed: Iterable[int] = (0,)) -> List[SweepConfig]:
    """Cartesian product helper: seeds x n x d x network x batch x algo."""
    return [SweepConfig(algo=a, n=nn, d=dd, network=net, batch=b,
                        rounds=rounds, seed=s)
            for s, nn, dd, net, b, a in itertools.product(
                seed, n, d, network, batch, algo)]


def _group_key(cfg: SweepConfig) -> Tuple:
    # one stacked engine call per group; reliable groups split by d so each
    # compiles at its own predecessor width (and overlaps on the thread pool)
    if cfg.engine_kind() == "reliable":
        return ("reliable", cfg.n, cfg.resolved_d(), cfg.rounds)
    return ("unreliable", cfg.n, cfg.rounds)


def _dedup_key(cfg: SweepConfig) -> Tuple:
    """Failure-free rounds are deterministic: the seed never changes the
    result, and the G_R degree is irrelevant to G_U dissemination.  Configs
    sharing this key are evaluated once and fanned back out."""
    d = cfg.resolved_d() if cfg.engine_kind() == "reliable" else None
    return (cfg.algo, cfg.n, d, cfg.network, cfg.batch, cfg.rounds)


def sweep(configs: Sequence[SweepConfig], *,
          window: Tuple[int, int] = (3, 10),
          engine: str = "vec") -> SweepResult:
    """Evaluate every config; returns per-config failure-free round latency,
    steady-state throughput and the full completion-time trajectories.
    ``engine="pallas"`` runs the inner relaxation on the tropical min-plus
    Pallas kernel (bit-for-bit equal to the default jnp path)."""
    all_configs = list(configs)

    # deterministic dedup: unique points computed, duplicates share results
    uniq: Dict[Tuple, int] = {}
    alias: List[int] = []
    configs = []
    for cfg in all_configs:
        key = _dedup_key(cfg)
        if key not in uniq:
            uniq[key] = len(configs)
            configs.append(cfg)
        alias.append(uniq[key])

    C = len(configs)
    med = np.full(C, np.nan)
    thr = np.full(C, np.nan)
    period = np.full(C, np.nan)
    completion: List[Optional[np.ndarray]] = [None] * C

    groups: Dict[Tuple, List[int]] = {}
    for i, cfg in enumerate(configs):
        groups.setdefault(_group_key(cfg), []).append(i)

    def run_group(item):
        key, idxs = item
        kind, n = key[0], key[1]
        rounds = key[-1]
        with span("tables"):
            if kind == "unreliable":
                tabs = [topology.unreliable_tables(
                    n, network=configs[i].network, batch=configs[i].batch,
                    mode=configs[i].algo) for i in idxs]
                run, fields = _engine.run_unreliable, ("parent", "send_off")
            else:
                tabs = [topology.reliable_tables(
                    n, d=configs[i].resolved_d(), network=configs[i].network,
                    batch=configs[i].batch) for i in idxs]
                run, fields = _engine.run_reliable, ("adj", "edge_off")
            arrays = [np.stack([getattr(t, f) for t in tabs])
                      for f in fields + ("occ", "prop")]
        rt = run(*arrays, rounds=rounds, engine=engine)
        for j, i in enumerate(idxs):
            one = _engine.RoundTimes(completion=rt.completion[j],
                                    start=rt.start[j],
                                    iterations=rt.iterations)
            s = _engine.summarize(one, mode=configs[i].algo, n=n,
                                 batch=configs[i].batch, window=window)
            med[i] = s["median_latency"]
            thr[i] = s["throughput"]
            period[i] = s["round_period"]
            completion[i] = rt.completion[j]

    # jit'd groups release the GIL while XLA runs: overlap them on a small
    # thread pool (each group writes disjoint result rows)
    from concurrent.futures import ThreadPoolExecutor
    items = list(groups.items())
    if len(items) > 1:
        with ThreadPoolExecutor(max_workers=min(4, len(items))) as ex:
            list(ex.map(run_group, items))
    elif items:
        run_group(items[0])

    alias_a = np.asarray(alias, dtype=np.intp)
    return SweepResult(configs=all_configs, median_latency=med[alias_a],
                       throughput=thr[alias_a], round_period=period[alias_a],
                       completion=[completion[a] for a in alias])
