"""Plain reference for failure-free AllConcur+, AllConcur and AllGather rounds.

A discrete-event loop over message arrivals, written from the protocol's
failure-free rules (arXiv:1708.08309, Algorithms 1-3 and 5) and the paper's
network model (Sec. IV), on the host in IEEE float64.  Nothing here imports
the program, and no table of it is read: the topology and the message sizes
are rebuilt from the deployment's own description.

- A server's NIC sends one message at a time, back to back, in the order
  the server issues them, from when it is free or the cause arrives,
  whichever is later.  A send takes ``bytes / 125 MB/s + 5 us``; the message
  arrives after the path's propagation delay.  Events at one time run in
  the order they were sent.
- G_U (AllConcur+ failure-free rounds, AllGather): each message travels the
  binomial tree rooted at its source.  The server at relative position
  ``p`` from the source forwards to ``p + 2^k`` for each ``2^k > p`` with
  ``p + 2^k < n``.  A message of the next round that reaches a server still
  in its round is held; the server forwards the held messages, in the order
  they came, when it enters that round.
- G_R (AllConcur): flooding over the circulant digraph whose successors of
  ``v`` are ``v + offset mod n``, in the order of the offsets.  A server
  forwards a message to all its successors on the first copy it receives in
  the message's round.  The first copy of the next round that arrives early
  is forwarded at once and forgotten when the round ends, so the message is
  forwarded again when its next copy arrives in-round.
- A server completes a round when it holds all ``n`` messages of it, enters
  the next round at once and A-broadcasts its own message to its first hops.
"""
from __future__ import annotations

import heapq
import math

import numpy as np

# ------------------------------------------------------------- the network
# paper Sec. IV: 1 GigE NICs, cut-through switches, a per-message software
# overhead at the sender; five European datacenters for the mdc deployment

NIC_BYTES_PER_S = 125e6
SEND_OVERHEAD = 5.0e-6
HOST_CABLE = 0.05e-6          # 10 m
SWITCH_CABLE = 0.5e-6         # 100 m
SWITCH_DELAY = 1.0e-6         # per switch crossed
FIBER_S_PER_KM = 5e-6
FIBER_STRETCH = 1.1
DCS = ("dublin", "london", "paris", "frankfurt", "stockholm")
DC_KM = {("dublin", "london"): 464, ("dublin", "paris"): 780,
         ("dublin", "frankfurt"): 1090, ("dublin", "stockholm"): 1625,
         ("london", "paris"): 455, ("london", "frankfurt"): 640,
         ("london", "stockholm"): 1440, ("paris", "frankfurt"): 480,
         ("paris", "stockholm"): 1545, ("frankfurt", "stockholm"): 1180}


def _fat_tree(servers: int, a: int, b: int) -> float:
    """One server per subnet of the smallest k-port fat tree (k even) with
    ``k^2 / 2 >= servers``; a pod holds ``k / 2`` subnets.  In one pod a
    path crosses 3 switches over 2 switch cables, else 5 over 4."""
    if a == b:
        return 0.0
    k = 2
    while k * k // 2 < servers:
        k += 2
    if a // (k // 2) == b // (k // 2):
        return 2 * HOST_CABLE + 2 * SWITCH_CABLE + 3 * SWITCH_DELAY
    return 2 * HOST_CABLE + 4 * SWITCH_CABLE + 5 * SWITCH_DELAY


def propagation(network: str, n: int) -> list:
    """``[n][n]`` one-way path delay in seconds.

    ``sdc``: one fat tree of ``n`` servers.  ``mdc``: server ``s`` in
    datacenter ``s mod 5`` at place ``s // 5`` of a local fat tree of
    ``max(ceil(n / 5), 2)`` servers; between datacenters a path crosses the
    local tree end to end (first to last place) plus the fiber.
    """
    if network == "sdc":
        return [[_fat_tree(n, a, b) for b in range(n)] for a in range(n)]
    if network != "mdc":
        raise ValueError(f"unknown network {network!r}")
    local = max((n + 4) // 5, 2)
    out = [[0.0] * n for _ in range(n)]
    for a in range(n):
        for b in range(n):
            da, db = DCS[a % 5], DCS[b % 5]
            if a == b:
                continue
            if da == db:
                out[a][b] = _fat_tree(local, a // 5, b // 5)
            else:
                km = DC_KM.get((da, db)) or DC_KM[(db, da)]
                out[a][b] = (_fat_tree(local, 0, local - 1)
                             + FIBER_STRETCH * km * FIBER_S_PER_KM)
    return out


# ------------------------------------------------------------ frame sizes
# the wire format of a protocol message: MAGIC(1) KIND(1) BODY_LEN(uvarint)
# BODY CRC32C(4); BODY = msgkind(uvarint) src(u32) epoch(u32) round(u64)
# eon(u32) payload(value) pad_len(uvarint) pad.  A value is a one-byte type
# tag, then a zigzag uvarint (int), or a uvarint length and the bytes
# (str), or a uvarint count and the items (tuple, dict: key then value).

TXN_BYTES = 250               # paper Sec. IV: a modelled transaction


def _uvarint_len(v: int) -> int:
    return max(1, (v.bit_length() + 6) // 7)


def value_len(v) -> int:
    if isinstance(v, bool) or v is None:
        return 1
    if isinstance(v, int):
        return 1 + _uvarint_len(2 * v if v >= 0 else -2 * v - 1)
    if isinstance(v, str):
        raw = len(v.encode("utf-8"))
        return 1 + _uvarint_len(raw) + raw
    if isinstance(v, (tuple, list)):
        return 1 + _uvarint_len(len(v)) + sum(value_len(x) for x in v)
    if isinstance(v, dict):
        return 1 + _uvarint_len(len(v)) + sum(
            value_len(k) + value_len(x) for k, x in v.items())
    raise TypeError(type(v).__name__)


def frame_bytes(payload, pad: int = 0) -> int:
    body = 1 + 4 + 4 + 8 + 4 + value_len(payload) + _uvarint_len(pad) + pad
    return 2 + _uvarint_len(body) + body + 4


def sweep_bytes(batch: int) -> int:
    """A round message of ``batch`` modelled transactions: the payload
    ``{"batch": batch}`` and ``batch * 250`` bytes of transaction bodies."""
    return frame_bytes({"batch": batch}, batch * TXN_BYTES)


def smr_bytes(batch: int, value_size: int) -> int:
    """A replicated-KV round message of ``batch`` puts with
    ``value_size``-byte values, client ids and keys below 64."""
    reqs = tuple((c % 64, 0, {"op": "put", "key": 0,
                              "value": ("v%d.0" % (c % 64)).ljust(value_size,
                                                                  "x")})
                 for c in range(batch))
    return frame_bytes({"kind": "smr", "src": 0, "round": 1, "batch": batch,
                        "reqs": reqs})


# ----------------------------------------------------------------- rounds

def _tree_hops(n: int, src: int, v: int) -> list:
    p = (v - src) % n
    out, k = [], 1
    while k < n:
        if k > p and p + k < n:
            out.append((src + p + k) % n)
        k *= 2
    return out


def timeline(algo: str, n: int, *, network: str, nbytes: int, rounds: int,
             offsets=None):
    """Entry and completion times ``[rounds, n]`` of rounds 1..``rounds``
    of every server, all servers starting round 1 at time 0.

    ``algo``: ``allconcur`` floods G_R (circulant ``offsets``);
    ``allconcur+`` and ``allgather`` disseminate over G_U.
    """
    reliable = algo == "allconcur"
    prop = propagation(network, n)
    if reliable:
        succ = [[(v + o) % n for o in offsets if (v + o) % n != v]
                for v in range(n)]
        hops = lambda src, v: succ[v]                          # noqa: E731
    else:
        tree = [[_tree_hops(n, s, v) for v in range(n)] for s in range(n)]
        hops = lambda src, v: tree[src][v]                     # noqa: E731
    send_s = nbytes / NIC_BYTES_PER_S + SEND_OVERHEAD
    entry = np.full((rounds + 1, n), np.nan)
    heap: list = []
    seq = 0
    nic_free = [0.0] * n
    rnd = [1] * n
    held = [set() for _ in range(n)]       # messages of the current round
    early = [[] for _ in range(n)]         # next round's, in arrival order
    waiting = n                            # servers not yet past ``rounds``

    def send(v, now, out):
        nonlocal seq
        t = max(now, nic_free[v])
        for dst, src, r in out:
            t += send_s
            heapq.heappush(heap, (t + prop[v][dst], seq, dst, src, r))
            seq += 1
        nic_free[v] = t

    def enter(v, now, out):
        nonlocal waiting
        r = rnd[v]
        if r <= rounds + 1:
            entry[r - 1, v] = now
        if r == rounds + 1:
            waiting -= 1
        if not reliable:                   # held G_U messages go on first
            for src in early[v]:
                held[v].add(src)
                out.extend((w, src, r) for w in hops(src, v))
        early[v] = []
        held[v].add(v)
        out.extend((w, v, r) for w in hops(v, v))

    def complete(v, now, out):
        while len(held[v]) == n:
            rnd[v] += 1
            held[v] = set()
            enter(v, now, out)

    for v in range(n):
        out: list = []
        enter(v, 0.0, out)
        send(v, 0.0, out)
    while waiting:
        now, _, v, src, r = heapq.heappop(heap)
        out = []
        if r == rnd[v]:
            if src not in held[v]:
                held[v].add(src)
                out.extend((w, src, r) for w in hops(src, v))
                complete(v, now, out)
        elif r == rnd[v] + 1 and src not in early[v]:
            early[v].append(src)
            if reliable:
                out.extend((w, src, r) for w in hops(src, v))
        send(v, now, out)
    return entry[:-1], entry[1:]


# ---------------------------------------------------------------- summary

def summary(algo: str, n: int, batch: int, entry, compl, window):
    """Median latency and throughput of one deployment's rounds.

    A server's own message of round ``k`` is A-delivered when round ``k``
    completes, or for AllConcur+ (whose unreliable rounds deliver one round
    late) when round ``k + 1`` completes; latency runs from the round's
    entry.  The median is over every server and delivered round (the mean
    of the middle two for an even count).  Throughput: ``t1`` and ``t2`` are
    the latest times any server makes its ``lo``-th and ``hi``-th delivery
    (each of ``n`` messages, ``batch`` transactions each); it is the mean
    over servers of the transactions delivered in ``(t1, t2]`` per second.
    """
    if algo == "allconcur+":
        deliver, lat = compl[1:], compl[1:] - entry[:-1]
    else:
        deliver, lat = compl, compl - entry
    lo, hi = window
    t1 = float(deliver[lo - 1].max())
    t2 = float(deliver[min(hi, len(deliver)) - 1].max())
    rates = [np.sum((deliver[:, v] > t1) & (deliver[:, v] <= t2))
             * n * batch / (t2 - t1) for v in range(n)]
    return float(np.median(lat)), float(np.mean(rates))


def deployment(algo: str, n: int, *, network: str, batch: int, rounds: int,
               window, offsets=None):
    """``(completion [rounds, n], median latency, throughput)`` of one
    failure-free sweep deployment."""
    entry, compl = timeline(algo, n, network=network,
                            nbytes=sweep_bytes(batch), rounds=rounds,
                            offsets=offsets)
    lat, thr = summary(algo, n, batch, entry, compl, window)
    return compl, lat, thr


def periodic_timeline(algo: str, n: int, *, network: str, nbytes: int,
                      rounds: int, offsets=None, prefix: int = 64):
    """Entry and completion ``[rounds, n]`` of a long failure-free run.

    The loop above gives the first ``prefix`` rounds.  With one message
    size, failure-free rounds settle into a fixed period; the reference
    checks that they have (every step of the second half of the prefix
    within 1e-12 of the period) and extends the prefix by that period,
    ``E[k] = E[p - 1] + (k - p + 1) * P``.
    """
    e, c = timeline(algo, n, network=network, nbytes=nbytes, rounds=prefix,
                    offsets=offsets)
    half = prefix // 2
    period = (e[-1] - e[half]) / (prefix - 1 - half)
    drift = float(np.max(np.abs(np.diff(e[half:], axis=0) - period)
                         / period))
    if drift > 1e-12 or not math.isfinite(drift):
        raise ValueError(f"no steady round period after {half} rounds: "
                         f"steps differ by {drift:.3e}")
    if rounds <= prefix:
        return e[:rounds], c[:rounds], period
    k = np.arange(1, rounds - prefix + 2, dtype=np.float64)[:, None]
    tail = e[-1] + k * period
    return (np.concatenate([e, tail[:-1]]), np.concatenate([c, tail[1:]]),
            period)
