"""Tensorized SMR client layer: arrivals -> batches -> acks, in one jit.

``benchmarks/smr_throughput.py`` replays clients one heap event at a time;
this module lifts the *client* dimension into jax so a million simulated
clients against thousands of (config x crash-schedule) deployments reduce to
a few array programs over the per-server round timelines that
:mod:`repro.vecsim.engine` (failure-free) and
:func:`repro.vecsim.failures.monte_carlo_times` (crash/eon-flip splices)
already produce.

The model (cross-validated at **zero tolerance** against
``build_smr_simulation`` in ``tests/test_vecsim_clients.py``):

- Clients are co-located round-robin: client ``cid`` submits to server
  ``cid % n`` and only that home server acks it
  (``SMRService._ack`` semantics).
- Each server serves its clients FIFO (``SMRService.pending`` order =
  submit-time order) in batches of at most ``batch_max`` per A-broadcast
  round.
- **Batch formation** is a segment-reduce + tiny scan.  With round ``r``
  (1-based) entered at ``E[r-1]`` and completed at ``C[r-1]``, let
  ``S_r = #{j : s_j <= E[r-1]}`` be the arrivals by the abcast of round
  ``r`` (the :mod:`repro.kernels.clients_segred` kernel).  The number of
  requests *served through* round ``r`` follows

      cum_r = min(S_r, cum_{r-delta} + batch_max),    cum_{<=0} = 0

  with ``delta = 2`` for DUAL (a request's payload rides two consecutive
  rounds — fresh in round ``a``, duplicate in ``a+1`` — so capacity taken
  in round ``a`` frees at ``a+2``) and ``delta = 1`` otherwise.  Request
  ``j`` (0-based FIFO rank) is then abcast in round
  ``a(j) = searchsorted(cum, j+1, side="left") + 1`` and acked at

      C[a(j)]      (DUAL: A-delivery lags one round)
      C[a(j) - 1]  (RELIABLE_ONLY / UNRELIABLE_ONLY)

  This recurrence is exact including overflow backlogs and partially-filled
  DUAL batches (new requests joining a duplicate round's spare capacity).
- **Closed-loop lockstep**: with ``cps <= batch_max`` clients per server all
  resubmitting on ack, generation ``g`` of every client on server ``h`` is
  abcast in lockstep; latency is ``C[g,h] - E[g,h]`` (non-dual) or
  ``C[2g+1,h] - E[2g,h]`` (DUAL) with no per-request state at all.

Exactness contract: given a round timeline, ack times equal the event
simulator's **bit-for-bit** (the ack is a gather of the same float, the
latency the same two floats subtracted).  The device computes only the
integer part — each request's abcast round, from exact order keys of the
times (:func:`order_keys`) — and the host gathers the floats, so the
contract holds on a TPU too, whose float64 is an emulation with fewer
mantissa bits.  End-to-end against
:mod:`repro.vecsim.engine` timelines the agreement is the engine's own
cross-validation tolerance (~1e-12 relative; float association in the
NIC scan), with SMR-sized cost tables from
:func:`repro.vecsim.topology.smr_message_bytes`.  Monte-Carlo timelines
are spliced *models* (see ``failures.py``) — the client mapping on top of
them is exact, the timeline itself is the approximation.

Percentiles use the repo-wide nearest-rank rule
(:mod:`repro.smr.percentiles`): ``idx = min(int(p * count), count - 1)``
over the ascending sort, on the host for both :func:`client_latencies` and
the schedule-pooled :func:`mc_client_latencies` (the device returns the
round index of every request of every schedule).  Integer time ticks
(ROADMAP Speed 3) would let the gather and the percentiles move back onto
the device.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import numpy as np

from ..smr.percentiles import nearest_rank_index
from ..smr.workload import ZipfianGenerator
from .engine import RoundTimes, run_reliable, run_unreliable
from .topology import reliable_tables, smr_message_bytes, unreliable_tables

MODES = ("allconcur+", "allconcur", "allgather")
PCTS = (0.50, 0.99, 0.999)


def _jax():
    import jax
    import jax.numpy as jnp
    return jax, jnp


def _delta(mode: str) -> int:
    """Rounds a request's payload occupies batch capacity (see module doc)."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    return 2 if mode == "allconcur+" else 1


# --------------------------------------------------------------------------
# key popularity (vectorized mirror of smr.workload)

def zipf_cdf(nkeys: int, theta: float = 0.99) -> np.ndarray:
    """The event generator's zipfian CDF, verbatim (same accumulation order,
    so both engines bisect the identical float array)."""
    return np.asarray(ZipfianGenerator(nkeys, theta)._cdf, dtype=np.float64)


def keys_from_uniform(u, cdf):
    """Map uniform draws ``u in [0, 1)`` to zipfian keys: the vectorized
    twin of ``ZipfianGenerator.draw`` — ``bisect_left`` == ``searchsorted
    side="left"`` — including the clamp to ``nkeys - 1`` for draws above a
    CDF whose float accumulation fell short of 1.0."""
    _, jnp = _jax()
    cdf = jnp.asarray(cdf)
    idx = jnp.searchsorted(cdf, jnp.asarray(u), side="left")
    return jnp.minimum(idx, cdf.shape[0] - 1).astype(jnp.int32)


def draw_keys(key, shape, *, distribution: str = "zipfian", nkeys: int = 256,
              theta: float = 0.99):
    """Seeded key stream of the given shape (int32 in ``[0, nkeys)``)."""
    jax, jnp = _jax()
    if distribution == "uniform":
        return jax.random.randint(key, shape, 0, nkeys, dtype=jnp.int32)
    if distribution != "zipfian":
        raise ValueError(f"distribution must be 'zipfian' or 'uniform', "
                         f"got {distribution!r}")
    u = jax.random.uniform(key, shape)
    return keys_from_uniform(u, zipf_cdf(nkeys, theta))


# --------------------------------------------------------------------------
# arrival streams

def arrival_times(seed: int, num_clients: int, requests_per_client: int,
                  rate: float) -> np.ndarray:
    """Open-loop submit times, ``[num_clients, requests_per_client]`` f64.

    Each client is an independent Poisson process of ``rate`` req/s, seeded
    by ``fold_in(PRNGKey(seed), cid)`` — per-client counters, so the stream
    of client ``cid`` is invariant to the population size and to whether the
    draw runs plain, jitted or vmapped.
    """
    if rate <= 0:
        raise ValueError(f"open-loop arrival requires rate > 0, got {rate!r}")
    jax, jnp = _jax()
    from ..kernels.compat import enable_x64
    with enable_x64():
        base = jax.random.PRNGKey(seed)

        def one(cid):
            k = jax.random.fold_in(base, cid)
            gaps = jax.random.exponential(
                k, (requests_per_client,), dtype=jnp.float64) / rate
            return jnp.cumsum(gaps)

        return np.asarray(jax.jit(jax.vmap(one))(jnp.arange(num_clients)))


def server_streams(arrivals, n: int) -> np.ndarray:
    """Group per-client arrivals into per-home-server FIFO streams.

    ``arrivals``: ``[num_clients, q]`` with client ``cid`` homed on
    ``cid % n`` (the event harness's ``assign_round_robin``).  Returns
    ``[n, (num_clients // n) * q]`` submit times, ascending per server.
    """
    arrivals = np.asarray(arrivals, dtype=np.float64)
    c, q = arrivals.shape
    if c % n:
        raise ValueError(f"num_clients={c} must be a multiple of n={n}")
    # cid = i * n + h  ->  [cps, n, q] -> per-server flat stream
    s = arrivals.reshape(c // n, n, q).transpose(1, 0, 2).reshape(n, -1)
    return np.sort(s, axis=1)


# --------------------------------------------------------------------------
# the jitted pipeline

def _counts_fn(engine: str):
    """``counts(s, edges, s_keys, edge_keys) -> [n, K]`` int32 arrivals by
    each edge: exact order keys for "vec", the Pallas kernel on the floats
    for "pallas"."""
    if engine == "pallas":
        from ..kernels.clients_segred import segment_counts
        return lambda s, e, s_keys, e_keys: segment_counts(s, e)
    if engine == "vec":
        return lambda s, e, s_keys, e_keys: _ascending_counts(s_keys, e_keys)
    raise ValueError(f"engine must be 'vec' or 'pallas', got {engine!r}")


def order_keys(x) -> Tuple[np.ndarray, np.ndarray]:
    """Exact ordering keys of float64 times, computed on the host.

    Returns ``(hi, lo)`` uint32 words whose lexicographic order is the
    numeric order of ``x`` (NaN-free; ``-0.0`` counts as ``+0.0``).  A TPU
    holds float64 as an emulated pair of float32 with fewer mantissa bits,
    so comparing the float values there can merge times that differ in
    their last bits; comparing these words is exact on every backend.
    """
    u = (np.asarray(x, np.float64) + 0.0).view(np.uint64)
    sign = np.uint64(1 << 63)
    key = np.where(u & sign, ~u, u | sign)
    return ((key >> np.uint64(32)).astype(np.uint32),
            (key & np.uint64(0xFFFFFFFF)).astype(np.uint32))


def _ascending_counts(s_keys, e_keys):
    """``#{j : s[h, j] <= edges[h, k]}`` for the pipeline's ascending FIFO
    rows, by a binary search per edge over :func:`order_keys` words.

    Equal to :func:`repro.kernels.clients_segred.segment_counts_reference`
    on sorted rows, without its sort (a sort of a 10^6-client stream takes
    the TPU's compiler minutes) and exact where the float values are not.
    """
    jax, jnp = _jax()
    (s_hi, s_lo), (e_hi, e_lo) = s_keys, e_keys
    m = s_hi.shape[-1]

    def row(s_hi, s_lo, q_hi, q_lo):
        def body(_, state):
            lo, hi = state
            mid = (lo + hi) // 2
            at = jnp.minimum(mid, m - 1)
            le = (s_hi[at] < q_hi) | ((s_hi[at] == q_hi) & (s_lo[at] <= q_lo))
            live = lo < hi
            return (jnp.where(live & le, mid + 1, lo),
                    jnp.where(live & ~le, mid, hi))

        lo = jnp.zeros(q_hi.shape, jnp.int32)
        hi = jnp.full(q_hi.shape, m, jnp.int32)
        lo, _ = jax.lax.fori_loop(0, max(m.bit_length(), 1), body, (lo, hi))
        return lo

    return jax.vmap(row)(s_hi, s_lo, e_hi, e_lo)


def _make_cum_scan(jax, jnp, delta: int, batch_max: int):
    def cum_scan(counts):
        # cum_r = min(S_r, cum_{r-delta} + batch_max); carry the last delta
        def step(carry, s_r):
            cur = jnp.minimum(s_r, carry[-1] + batch_max)
            return (cur,) + carry[:-1], cur

        init = (jnp.zeros(counts.shape[0], counts.dtype),) * delta
        _, cum = jax.lax.scan(step, init, counts.T)
        return cum.T                                    # [n, K]

    return cum_scan


@functools.lru_cache(maxsize=None)
def _compiled_pipeline(delta: int, batch_max: int, engine: str):
    """One jit: segment-reduce -> capacity scan -> round assignment, mapped
    over timelines.  Its results are integers (the abcast round of every
    request), exact on any backend; the float ack gather, the latencies and
    the percentiles are taken from them on the host (:func:`_host_gather`),
    so they stay bit-exact where the device's float64 is emulated."""
    jax, jnp = _jax()
    _counts = _counts_fn(engine)
    cum_scan = _make_cum_scan(jax, jnp, delta, batch_max)

    def pipeline(entry, s, e_keys, s_keys):
        # entry: [T, 1 or n, K] round entries per timeline (one row shared
        # by every server, or one per server); s: [n, M] submits
        n, m = s.shape
        k = entry.shape[-1]
        ranks = jnp.arange(1, m + 1, dtype=jnp.int32)

        def one(rows):
            e, e_hi, e_lo = (jnp.broadcast_to(x, (n, k)) for x in rows)
            counts = _counts(s, e, s_keys, (e_hi, e_lo))  # [n, K] int32
            cum = cum_scan(counts)
            return jax.vmap(
                lambda c: jnp.searchsorted(c, ranks, side="left"))(cum)

        return jax.lax.map(one, (entry,) + tuple(e_keys))   # [T, n, M]

    return jax.jit(pipeline)


def _assign_rounds(entry, s, *, delta: int, batch_max: int,
                   engine: str) -> np.ndarray:
    """0-based abcast round of every request, ``[T, n, M]`` int32, for
    ``[T, 1 or n, K]`` round entries against ``[n, M]`` submits."""
    from ..kernels.compat import enable_x64
    fn = _compiled_pipeline(delta, int(batch_max), engine)
    with enable_x64():
        return np.asarray(fn(entry, s, order_keys(entry), order_keys(s)))


def _host_gather(a0, ack_times, s, lag: int):
    """Acks, latencies and the served mask of round assignments ``a0``
    against ``ack_times`` (same rank; leading axes broadcast), on the host
    in IEEE float64."""
    k = ack_times.shape[-1]
    ack_idx = a0 + lag
    valid = (ack_idx < k) & np.isfinite(s)
    ack = np.take_along_axis(ack_times, np.minimum(ack_idx, k - 1), axis=-1)
    return ack, ack - s, valid


def _pooled_percentiles(served: np.ndarray, ps) -> dict:
    """Nearest-rank percentiles of the 1-D ``served`` latencies (any order;
    partitioned in place); NaN when empty."""
    if not served.size:
        return {p: float("nan") for p in ps}
    idx = [nearest_rank_index(served.size, float(p)) for p in ps]
    served.partition(sorted(set(idx)))
    return {p: float(served[i]) for p, i in zip(ps, idx)}


@dataclass(frozen=True)
class ClientLatencies:
    """Per-request results of one deployment (or one spliced schedule)."""
    round_idx: np.ndarray    # [n, M] 0-based abcast round (K = unserved)
    ack: np.ndarray          # [n, M] ack times (garbage where ~valid)
    latency: np.ndarray      # [n, M] ack - submit
    valid: np.ndarray        # [n, M] served within the timeline horizon
    percentiles: dict        # {p: seconds} pooled nearest-rank
    served: int              # valid request count


def client_latencies(entry, ack_times, submits, *, mode: str,
                     batch_max: int, ack_lag: Optional[int] = None,
                     engine: str = "vec",
                     ps: Sequence[float] = PCTS) -> ClientLatencies:
    """Open-loop client latencies against one per-server round timeline.

    ``entry[h, k]`` / ``ack_times[h, k]``: entry and *ack source* time of
    (1-based) round ``k+1`` on server ``h``.  For engine timelines pass
    ``entry = times.start.T`` and ``ack_times = times.completion.T``; the
    DUAL one-round delivery lag is applied here (``ack_lag = 1``).  For
    Monte-Carlo timelines pass ``failures.MonteCarloTimes.entry/deliver``
    (broadcast per server) with ``ack_lag = 0`` — the splice already folds
    the lag into ``deliver``.

    ``submits[h, j]``: ascending per-server FIFO submit times
    (:func:`server_streams`); ``+inf`` marks ragged padding.
    """
    lag = (1 if mode == "allconcur+" else 0) if ack_lag is None else ack_lag
    if batch_max < 1:
        raise ValueError(f"batch_max must be >= 1, got {batch_max}")
    entry = np.asarray(entry, np.float64)
    s = np.asarray(submits, np.float64)
    a0 = _assign_rounds(entry[None], s, delta=_delta(mode),
                        batch_max=batch_max, engine=engine)[0]
    ack, lat, valid = _host_gather(a0, np.asarray(ack_times, np.float64), s,
                                   lag)
    served = lat[valid]
    return ClientLatencies(round_idx=a0, ack=ack, latency=lat, valid=valid,
                           percentiles=_pooled_percentiles(served, ps),
                           served=int(served.size))


def mc_client_latencies(mc_entry, mc_deliver, submits, *, mode: str,
                        batch_max: int, engine: str = "vec",
                        ps: Sequence[float] = PCTS) -> dict:
    """Client percentiles pooled across Monte-Carlo schedules.

    ``mc_entry`` / ``mc_deliver``: ``[S, R]`` spliced timelines
    (:func:`repro.vecsim.failures.monte_carlo_times`) shared by all ``n``
    servers of the symmetric deployment; ``submits``: ``[n, M]`` per-server
    streams replayed against every schedule.  Returns pooled nearest-rank
    percentiles plus the served-request count.
    """
    entry = np.asarray(mc_entry, np.float64)
    s = np.asarray(submits, np.float64)
    a0 = _assign_rounds(entry[:, None, :], s, delta=_delta(mode),
                        batch_max=batch_max, engine=engine)   # [S, n, M]
    _ack, lat, valid = _host_gather(
        a0, np.asarray(mc_deliver, np.float64)[:, None, :], s, 0)
    del a0, _ack
    served = lat[valid]
    del lat, valid
    return {"percentiles": _pooled_percentiles(served, ps),
            "served": int(served.size), "schedules": int(entry.shape[0])}


# --------------------------------------------------------------------------
# closed-loop lockstep (no per-request state at all)

def closed_loop_latencies(times: RoundTimes, *, mode: str, batch_max: int,
                          clients_per_server: int) -> np.ndarray:
    """Latency per (generation, server) under closed-loop lockstep.

    With ``clients_per_server <= batch_max`` clients all submitting at t=0
    and resubmitting on ack, every server's batches stay in lockstep:
    generation ``g`` is abcast as one full batch in round ``g+1`` (non-dual)
    or round ``2g+1`` (DUAL, where odd rounds carry only duplicates).
    Returns ``[..., G, n]``; each entry is the identical latency of all
    ``clients_per_server`` clients of that server (uniform weights, so
    pooled nearest-rank percentiles over this array equal the per-request
    ones).
    """
    if clients_per_server > batch_max:
        raise ValueError(
            f"lockstep requires clients_per_server <= batch_max, got "
            f"{clients_per_server} > {batch_max} (use the open-loop path)")
    _delta(mode)  # validates mode
    c = np.asarray(times.completion)
    e = np.asarray(times.start)
    k = c.shape[-2]
    if mode == "allconcur+":
        g = k // 2   # gen g: abcast at E[2g], acked at C[2g+1]
        return c[..., 1::2, :][..., :g, :] - e[..., ::2, :][..., :g, :]
    return c - e


# --------------------------------------------------------------------------
# SMR-sized engine timelines

def smr_round_times(mode: str, n: int, *, reqs_per_round: int, rounds: int,
                    network: str = "sdc", value_size: int = 16,
                    batch_cap: Optional[int] = None,
                    engine: str = "vec") -> RoundTimes:
    """Failure-free round timeline with SMR-sized messages.

    Cost tables are built with ``nbytes = smr_message_bytes(mode,
    reqs_per_round)`` — the constant representative frame carrying
    ``reqs_per_round`` put requests — so the vectorized timeline charges the
    same wire bytes the event simulator's SMR payloads serialize to (exact
    within the small-varint band; see :func:`smr_message_bytes`).
    ``engine`` is forwarded to the round engine ("vec" | "pallas").
    """
    nbytes = smr_message_bytes(mode, reqs_per_round, value_size=value_size)
    if mode == "allconcur":
        t = reliable_tables(n, network=network, mode=mode, nbytes=nbytes)
        return run_reliable(t.adj, t.edge_off, t.occ, t.prop, rounds=rounds,
                            engine=engine)
    t = unreliable_tables(n, network=network, mode=mode, nbytes=nbytes)
    return run_unreliable(t.parent, t.send_off, t.occ, t.prop, rounds=rounds,
                          engine=engine)
