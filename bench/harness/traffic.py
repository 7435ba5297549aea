"""The general traffic generator: seeds, queries and arrivals from data.

Every traffic file (``bench/traffic/<name>.json``) is parameters only; the
functions here turn them and ``--seed`` into the inputs of each answer.  The
same seed gives the same inputs, and every seed gives the same amount of
work, in another order.
"""
from __future__ import annotations

import numpy as np

WARMUP = 2 ** 32 - 1          # answer index of the warm-up, never timed


def stream(seed: int, *words: int) -> np.random.Generator:
    """An independent generator for ``(seed, *words)``; any whole ``seed``."""
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) % 2 ** 64, *[int(w) for w in words]]))


def small_seed(seed: int, *words: int) -> int:
    """A seed in ``[0, 2**31)`` for a program that takes a 32-bit seed."""
    return int(stream(seed, *words).integers(0, 2 ** 31))


def query_batches(config: dict, traffic: dict, seed: int, index: int):
    """Batch sizes of query ``index`` of a sweep traffic.

    Every size of the configuration's ``batch_range`` is asked once, in an
    order permuted from ``seed``, ``per_query`` to a query; query ``index``
    past the last block starts a new permutation.  The warm-up asks the last
    block (:func:`warmup_query`), which a window that holds fewer queries
    than there are blocks never reaches, so no window asks a deployment that
    set-up or the window has asked before.
    """
    lo, hi = config["batch_range"]
    per = int(traffic["per_query"])
    sizes = np.arange(lo, hi + 1)
    cycle, pos = divmod(index, sizes.size // per)
    perm = stream(seed, 1, cycle).permutation(sizes)
    return sorted(int(b) for b in perm[pos * per:(pos + 1) * per])


def warmup_query(config: dict, traffic: dict) -> int:
    """Index of the query that set-up asks: the first permutation's last."""
    lo, hi = config["batch_range"]
    return (hi - lo + 1) // int(traffic["per_query"]) - 1


def poisson_arrivals(seed: int, index: int, *, clients: int,
                     requests_per_client: int, rate: float) -> np.ndarray:
    """Open-loop submit times ``[clients, q]``: every client an independent
    Poisson process of ``rate`` requests per second, drawn from
    ``(seed, index)``."""
    gaps = stream(seed, 2, index).exponential(
        1.0 / rate, (clients, requests_per_client))
    return np.cumsum(gaps, axis=1)


def horizon_rounds(config: dict, traffic: dict) -> int:
    """Rounds that drain the whole backlog: ``spans`` times the mean arrival
    span of a server's clients, in rounds of ``batch_max / 2`` requests at
    the configured utilisation, plus 64 (``benchmarks/sweep_vec.smr_shape``)."""
    cps = config["clients"] // config["n"]
    q = traffic["requests_per_client"]
    base = int(cps * q / (config["util"] * config["batch_max"] / 2))
    rounds = traffic["horizon_mean_spans"] * base + 64
    if rounds != traffic["rounds"]:
        raise ValueError(f"traffic states {traffic['rounds']} rounds, its "
                         f"parameters give {rounds}")
    return rounds
