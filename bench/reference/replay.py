"""Plain references for the client cells, on the host in IEEE float64.

Nothing here imports the program (``repro``).  The client layer and the
crash splice are straightforward numpy loops over the semantics of
``repro.vecsim.clients`` and ``repro.vecsim.failures``; the failure-free
round timelines they run on come from :mod:`bench.reference.rounds`.
"""
from __future__ import annotations

import numpy as np

BIG = 1e12          # the splice's "no further crash" sentinel


# ------------------------------------------------------------ client layer

def home_streams(arrivals, n: int) -> np.ndarray:
    """Per-server FIFO submit times ``[n, clients / n * q]`` of
    ``[clients, q]`` arrivals, client ``c`` homed on server ``c mod n``."""
    c, q = arrivals.shape
    return np.sort(arrivals.reshape(c // n, n, q).transpose(1, 0, 2)
                   .reshape(n, -1), axis=1)


def round_index(entry, submits, *, delta: int, batch_max: int):
    """0-based abcast round of every request: arrivals by each round's
    entry, the capacity recurrence ``cum_r = min(S_r, cum_{r-delta} +
    batch_max)``, and each FIFO rank's first round with capacity for it.

    ``entry[..., K]`` round entries against ascending ``submits[..., M]``
    (leading axes broadcast); returns ``[..., M]`` int32, ``K`` where the
    horizon never serves the request.
    """
    lead = np.broadcast_shapes(entry.shape[:-1], submits.shape[:-1])
    entry = np.broadcast_to(entry, lead + entry.shape[-1:])
    submits = np.broadcast_to(submits, lead + submits.shape[-1:])
    rows, k = list(np.ndindex(lead)), entry.shape[-1]
    counts = np.empty((k, len(rows)), np.int64)
    for b, i in enumerate(rows):
        counts[:, b] = np.searchsorted(submits[i], entry[i], side="right")
    cum = np.zeros((k, len(rows)), np.int64)
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


def latencies(a0, ack_times, submits, lag: int):
    """Latencies of the served requests (1-D) for round assignments ``a0``:
    the ack is ``ack_times[a0 + lag]``, served while that lies within the
    horizon."""
    k = ack_times.shape[-1]
    idx = a0 + lag
    valid = (idx < k) & np.isfinite(submits)
    ack = np.take_along_axis(np.broadcast_to(ack_times, idx.shape[:-1]
                                             + ack_times.shape[-1:]),
                             np.minimum(idx, k - 1), axis=-1)
    return (ack - submits)[valid]


def nearest_rank(served: np.ndarray, ps) -> dict:
    """``idx = min(int(p * count), count - 1)`` over the ascending sort."""
    if not served.size:
        return {p: float("nan") for p in ps}
    srt = np.sort(served)
    return {p: float(srt[min(int(p * srt.size), srt.size - 1)]) for p in ps}


# ------------------------------------------------------------ crash splice

def splice(du: float, dr: float, crash_times, *, rounds: int,
           fd_timeout: float):
    """Spliced failure timelines ``entry, deliver [S, rounds]`` and crash
    counts ``[S]``, one row per schedule of sorted crash times ``[S, F]``.

    A round of length ``du`` runs unless the next crash lands before it
    ends; then the elapsed prefix is wasted, detection costs ``fd_timeout``,
    two reliable rounds of ``dr`` repair it, and its messages deliver at the
    end of the first.  Unreliable rounds deliver one round late (``2 du``).
    A crash that lands inside a recovery is detected when it ends.  The
    clock is a compensated sum, exact to well below a round.
    """
    crash_times = np.asarray(crash_times, np.float64)
    s, f = crash_times.shape
    nxt_table = np.concatenate([crash_times, np.full((s, 1), BIG)], axis=1)
    rows = np.arange(s)
    t = np.zeros(s)
    tc = np.zeros(s)
    ptr = np.zeros(s, np.int64)
    entry = np.empty((s, rounds))
    deliver = np.empty((s, rounds))
    for k in range(rounds):
        now = t + tc
        nxt = nxt_table[rows, ptr]
        crashed = nxt < now + du
        rec1 = (np.maximum(nxt, now) - now) + fd_timeout + dr
        step = np.where(crashed, rec1 + dr, du)
        entry[:, k] = now
        deliver[:, k] = now + np.where(crashed, rec1, 2.0 * du)
        t_next = t + step
        tc = tc + np.where(np.abs(t) >= np.abs(step),
                           (t - t_next) + step, (step - t_next) + t)
        t = t_next
        ptr = ptr + crashed
    return entry, deliver, ptr
