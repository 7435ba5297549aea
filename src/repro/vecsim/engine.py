"""Batched min-plus round recurrence for failure-free AllConcur+/AllGather.

A failure-free round is a *deterministic* function of the overlay digraph and
the network model: message ``s`` reaches server ``v`` along overlay edges, and
the arrival time is a tropical (min-plus) path sum

    T[s, v] = min_u ( cost[s, u, v] + T[s, u] )

iterated to fixpoint (``jnp.min(cost + t[..., None, :], axis=-1)`` shape).
The one non-local ingredient is the sender NIC: the event simulator
serializes each drain's sends back-to-back at link bandwidth, so an edge's
cost depends on *when* its message reaches the head of the sender's queue.
We therefore alternate two vectorized passes until the joint fixpoint:

1. **NIC pass** — per server, sort all (round, message) forward events by
   their enqueue time and replay the FIFO NIC with a cumulative max-plus scan
   (``free_i = max(E_i, free_{i-1}) + occ_i``, computed with cumsum+cummax,
   no sequential loop).
2. **min-plus pass** — propagate send-completion times along overlay edges to
   get the next arrival estimates.

Both passes are pure array programs: they vmap over a batch of configs and
jit cleanly; the inner relaxation optionally dispatches to the Pallas
tropical-semiring kernel (``engine="pallas"``, bit-for-bit equal to the
jnp path — see README and ``repro.kernels.tropical``).  All K rounds are
relaxed jointly, which captures the pipelining
the protocol actually exhibits: round k+1 messages overtake stragglers of
round k and are postponed (G_U) or forwarded early (G_R) exactly like in the
event engine.

Semantics replicated from ``repro.sim.runner`` / ``repro.core.server``:

- G_U rounds (AllConcur+ failure-free, AllGather): source-rooted binomial
  trees; a round-(k+1) message reaching a server still in round k is
  *postponed* and forwarded only at the server's round transition.
- G_R rounds (AllConcur): flood with per-server forward-on-first-receipt;
  a round-(k+1) message reaching a server still in round k is forwarded
  immediately but *dropped* from the round state at the transition
  (``M_next`` is cleared), so it is re-forwarded and only *installed* when
  the next copy arrives in-round.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Tuple

import numpy as np

from .spans import span

BIG = 1e12          # "not yet known" sentinel (finite: avoids inf-inf NaNs)
ENGINES = ("vec", "pallas")   # jnp gather relaxation | Pallas tropical kernel
_EPS = 1e-9         # fixpoint convergence tolerance (seconds): one ns is 4+
                    # orders below any reported latency; tighter values only
                    # chase float-rounding churn through the round pipeline


def _jax():
    import jax
    import jax.numpy as jnp
    return jax, jnp


@dataclass(frozen=True)
class RoundTimes:
    """Per-config round trajectory: ``completion[k, v]`` is the time server v
    completes round k+1 (k = 0..K-1); ``start[k, v]`` is its entry time."""
    completion: np.ndarray   # [..., K, n]
    start: np.ndarray        # [..., K, n]
    iterations: int


def _nic_scan(jnp, keys, occ, tx0):
    """Replay one server's FIFO NIC over its forward events.

    keys: lexsort key tuple, last key primary — and the primary key must be
    the enqueue time E.  Ties beyond the explicit keys fall back to flat
    item order (lexsort is stable), which encodes (round, source, event
    kind) by construction at every call site.  occ [m] is each event's NIC
    occupancy; tx0 is the NIC free time carried in from earlier (frozen)
    events.  Returns (start times [m], final free time): start is when each
    event's first send begins serializing — replicating the event heap's
    drain order.

    The TPU's compiler spends minutes on a float64 sort of a G_R event list
    (m = 2Kn) and on a float64 ``cumsum`` (a reduce-window), so on the TPU
    the same stable order comes from comparison counts and the prefix
    scans are associative scans (:func:`_nic_scan_tpu`).  Every other
    backend keeps the sort and the cumulative primitives.
    """
    import jax
    return jax.lax.platform_dependent(
        keys, occ, tx0,
        tpu=lambda k, o, t: _nic_scan_tpu(jnp, k, o, t),
        default=lambda k, o, t: _nic_scan_sorted(jnp, k, o, t))


def _nic_free(jnp, E_s, occ_s, tx0, cumsum, cummax):
    """NIC free times of events already in FIFO order (max-plus closed form:
    ``free_i = max(E_i, free_{i-1}) + occ_i`` via prefix sum and max)."""
    csum = cumsum(occ_s)
    prev = csum - occ_s
    return csum + jnp.maximum(cummax(E_s - prev), tx0)


def _nic_scan_sorted(jnp, keys, occ, tx0):
    import jax.lax as lax
    E = keys[-1]
    order = jnp.lexsort(keys)
    E_s, occ_s = E[order], occ[order]
    free = _nic_free(jnp, E_s, occ_s, tx0, jnp.cumsum,
                     lambda x: lax.cummax(x, axis=0))
    start_sorted = free - occ_s
    return jnp.zeros_like(E).at[order].set(start_sorted), free[-1]


def _nic_scan_tpu(jnp, keys, occ, tx0):
    import jax.lax as lax
    E = keys[-1]
    m = E.shape[0]
    idx = jnp.arange(m, dtype=jnp.int32)
    # before[i, j]: item j precedes item i in the stable lexsort (keys are
    # times and round numbers: no NaN, no negative zero)
    before = idx[None, :] < idx[:, None]
    for k in keys:                                 # least significant first
        ki, kj = k[:, None], k[None, :]
        before = (kj < ki) | ((kj == ki) & before)
    rank = jnp.sum(before, axis=1, dtype=jnp.int32)
    order = jnp.zeros(m, jnp.int32).at[rank].set(idx)
    E_s, occ_s = E[order], occ[order]
    free = _nic_free(jnp, E_s, occ_s, tx0,
                     lambda x: lax.associative_scan(jnp.add, x),
                     lambda x: lax.associative_scan(jnp.maximum, x))
    return free[rank] - occ, free[-1]


# ---------------------------------------------------------------------------
# G_U rounds: binomial-tree dissemination with postponement
# ---------------------------------------------------------------------------
#
# Postponement makes G_U rounds *sequential per server*: every round-k NIC
# event has E <= C_k[v] and every round-(k+1) event has E >= C_k[v], so the
# whole trajectory is a lax.scan over rounds carrying (round entry times,
# NIC free times), with a small per-round fixpoint inside (~tree depth
# iterations over [n, n] arrays instead of a joint K-round relaxation).

def _unreliable_round(jax, jnp, tstart, tx0, parent, send_off, occ, prop,
                      prop_from_parent, max_iters, relax_cost=None):
    n = tstart.shape[0]
    eye = jnp.eye(n, dtype=bool)
    tsv = tstart[None, :]                      # round entry, per server column

    def passes(A):
        # processing-ready time: own message at round entry; received
        # messages clamp to round entry (postponed until the transition).
        # Sort keys (E, then arrival order, then flat index = source id):
        # postponed messages flush in arrival order before the own message.
        E = jnp.where(eye, tsv, jnp.maximum(A, tsv))
        Aeff = jnp.where(eye, tsv, A)          # tie key: real arrival order
        start, free_end = jax.vmap(
            lambda Ev, Av, ov, t0: _nic_scan(jnp, (Av, Ev), ov, t0),
            in_axes=(1, 1, 1, 0), out_axes=(1, 0))(E, Aeff, occ, tx0)
        if relax_cost is None:
            cand = (jnp.take_along_axis(start, parent, axis=1)
                    + send_off + prop_from_parent)
        else:
            # tropical kernel: per-source (1, n) x (n, n) min-plus — the one
            # finite entry per column is the parent edge, so the min-plus
            # contraction reproduces the tree gather bit-for-bit.  prop is
            # added after the min (single candidate: equivalent) to keep the
            # event sim's (start + send_off) + prop float association
            from ..kernels.tropical import tropical_matmul
            cand = tropical_matmul(start[:, None, :], relax_cost)[:, 0, :] \
                + prop_from_parent
        A_new = jnp.where(eye, tsv, cand)
        return A_new, E, free_end

    def cond(state):
        A, it, delta = state
        return (it < max_iters) & (delta > _EPS)

    def body(state):
        A, it, _ = state
        A_new, _E, _f = passes(A)
        delta = jnp.max(jnp.abs(jnp.clip(A_new, 0, BIG) - jnp.clip(A, 0, BIG)))
        return A_new, it + 1, delta

    A0 = jnp.where(eye, tsv, jnp.full((n, n), BIG, tstart.dtype))
    A, it, _ = jax.lax.while_loop(cond, body, (A0, jnp.int32(0),
                                               jnp.float64(BIG)))
    _A, E, free_end = passes(A)
    C = jnp.max(E, axis=0)                     # completion: last processing
    return C, free_end, it


def run_unreliable(parent, send_off, occ, prop, *, rounds: int,
                   max_iters: int = 0, engine: str = "vec") -> RoundTimes:
    """Relax K failure-free G_U rounds.  Batched: all array arguments may
    carry leading batch dimensions (vmapped out here).  ``engine="pallas"``
    lowers the relaxation onto the tropical min-plus kernel (bit-for-bit
    equal to the default jnp path; interpret-mode off-TPU)."""
    jax, jnp = _jax()
    from ..kernels.compat import enable_x64
    if engine not in ENGINES:
        raise ValueError(f"engine must be one of {ENGINES}, got {engine!r}")
    parent = np.asarray(parent)
    batch_shape = parent.shape[:-2]
    n = parent.shape[-1]
    K = rounds
    if not max_iters:
        max_iters = 2 * int(np.ceil(np.log2(max(n, 2)))) + 8

    def flat(a):
        return np.asarray(a, np.float64).reshape(
            (-1,) + a.shape[len(batch_shape):])

    with span("dispatch"), enable_x64():
        fn = _compiled_unreliable(n, K, max_iters, engine)
        C, tstart, iters = fn(
            parent.reshape((-1, n, n)).astype(np.int32),
            flat(np.asarray(send_off)), flat(np.asarray(occ)),
            flat(np.asarray(prop)))
    C = np.asarray(C).reshape(batch_shape + (K, n))
    tstart = np.asarray(tstart).reshape(batch_shape + (K, n))
    with span("sync"):
        iterations = int(np.max(iters))
    return RoundTimes(completion=C, start=tstart, iterations=iterations)


@functools.lru_cache(maxsize=64)
def _compiled_unreliable(n: int, K: int, max_iters: int,
                         engine: str = "vec"):
    jax, jnp = _jax()
    from ..kernels.compat import enable_x64

    use_pallas = engine == "pallas"

    with enable_x64():
        def single(parent, send_off, occ, prop):
            prop_from_parent = prop[parent, jnp.arange(n)[None, :]]
            relax_cost = None
            if use_pallas:
                # dense per-source send-slot costs: the only finite entry in
                # column (s, :, v) is the parent edge of v in s's tree
                # (propagation is added after the contraction)
                s_idx = jnp.arange(n)[:, None]
                v_idx = jnp.arange(n)[None, :]
                relax_cost = jnp.full((n, n, n), jnp.inf, jnp.float64).at[
                    s_idx, parent, v_idx].set(send_off)

            def round_step(carry, _):
                tstart, tx0 = carry
                C, free_end, it = _unreliable_round(
                    jax, jnp, tstart, tx0, parent, send_off, occ, prop,
                    prop_from_parent, max_iters, relax_cost)
                return (C, free_end), (tstart, C, it)

            init = (jnp.zeros(n, jnp.float64), jnp.zeros(n, jnp.float64))
            _carry, (ts, C, its) = jax.lax.scan(round_step, init, None,
                                                length=K)
            return C, ts, jnp.max(its)

        # the program's name is what compile logs and profiles report
        single.__name__ = f"vecsim_unreliable_n{n}_k{K}_{engine}"
        return jax.jit(jax.vmap(single))


# ---------------------------------------------------------------------------
# G_R rounds: flood dissemination with early-forward + install
# ---------------------------------------------------------------------------

def _reliable_step(jax, jnp, A1, inst, tstart, pred, pred_cost, pred_mask,
                   occ, t0, pallas_tables=None):
    """One Jacobi sweep of the joint K-round G_R relaxation.

    ``pred[v, j]`` lists v's G_R predecessors (padded, masked by
    ``pred_mask``); ``pred_cost[v, j]`` is that edge's send-slot offset plus
    propagation, so candidates gather over d predecessors instead of a dense
    n^3 min-plus contraction.  With ``pallas_tables`` the same relaxation
    runs as a dense tropical-kernel min-plus over (cost2, has_pad) —
    bit-for-bit equal to the gather (see run_reliable).
    """
    K, n, _ = A1.shape
    k_idx = jnp.arange(K)
    eye = jnp.eye(n, dtype=bool)
    tsv = tstart[:, None, :]

    # event 1: first receipt (own message: round entry).  event 2: install
    # re-forward, only when the first copy came early (A1 < round entry).
    E1 = jnp.where(eye[None], tsv, A1)
    early = (~eye[None]) & (A1 < tsv)
    E2 = jnp.where(early, inst, BIG)

    occ_b = jnp.broadcast_to(occ[None, None, :], (K, n, n))
    rnd_b = jnp.broadcast_to(k_idx[:, None, None], (K, n, n)).astype(
        jnp.float64)

    def per_server(E1v, E2v, rv, ov):
        # sort keys (E, then round — a completing drain serializes the
        # finishing round's forwards before the next round's A-broadcast —
        # then flat order: round-k first receipts by source, then installs)
        E = jnp.concatenate([E1v.ravel(), E2v.ravel()])
        r = jnp.concatenate([rv.ravel(), rv.ravel()])
        o = jnp.where(E >= BIG, 0.0, jnp.concatenate([ov.ravel(), ov.ravel()]))
        st, _free = _nic_scan(jnp, (r, E), o, jnp.float64(0.0))
        return st[: K * n].reshape(K, n), st[K * n:].reshape(K, n)

    start1, start2 = jax.vmap(per_server, in_axes=(2, 2, 2, 2),
                              out_axes=2)(E1, E2, rnd_b, occ_b)

    if pallas_tables is None:
        # min-plus over G_R edges: gather both forward events of each
        # predecessor
        c1 = start1[:, :, pred] + pred_cost[None, None]   # [K, s, v, dmax]
        c2 = start2[:, :, pred] + pred_cost[None, None]
        c1 = jnp.where(pred_mask[None, None], c1, BIG)
        c2 = jnp.where(pred_mask[None, None], c2, BIG)
        cand = jnp.concatenate([c1, c2], axis=-1)         # [K, s, v, 2*dmax]
        A1_new = jnp.min(cand, axis=-1)
        in_round = jnp.where(cand >= tsv[..., None], cand, BIG)
        inst_new = jnp.min(in_round, axis=-1)
    else:
        # dense tropical min-plus: both forward events stack along the
        # contraction axis (same cost matrix), the install rule becomes the
        # kernel's threshold gate, and columns whose gather rows carried
        # BIG padding (in-degree < dmax) get the same min(., BIG) cap
        from ..kernels.tropical import tropical_matmul_threshold
        cost2, has_pad = pallas_tables                    # [2n, n], [n]
        S2 = jnp.concatenate([start1, start2], axis=-1)   # [K, s, 2n]
        thr = jnp.broadcast_to(tsv, (K, n, n))
        plain, gated = tropical_matmul_threshold(S2, cost2, thr, big=BIG)
        pad = has_pad[None, None, :]
        A1_new = jnp.where(pad, jnp.minimum(plain, BIG), plain)
        inst_new = jnp.where(pad, jnp.minimum(gated, BIG), gated)
    A1_new = jnp.where(eye[None], tsv, A1_new)
    inst_new = jnp.where(eye[None], tsv, inst_new)

    C = jnp.max(inst_new, axis=1)
    tstart_new = jnp.concatenate([jnp.full((1, n), t0, A1.dtype), C[:-1]], 0)
    return A1_new, inst_new, tstart_new, C


def run_reliable(adj, edge_off, occ, prop, *, rounds: int,
                 max_iters: int = 0, engine: str = "vec") -> RoundTimes:
    """Relax K failure-free G_R (AllConcur) rounds to the joint fixpoint.

    G_R rounds interleave on the NIC (early forwards of round k+1 run while
    round k drains), so all K rounds relax jointly rather than sequentially.
    ``engine="pallas"`` lowers the flood relaxation onto the tropical
    min-plus kernel, bit-for-bit equal to the default jnp gather path.
    """
    jax, jnp = _jax()
    from ..kernels.compat import enable_x64
    if engine not in ENGINES:
        raise ValueError(f"engine must be one of {ENGINES}, got {engine!r}")
    adj = np.asarray(adj).astype(bool)
    batch_shape = adj.shape[:-2]
    n = adj.shape[-1]
    K = rounds
    if not max_iters:
        max_iters = 3 * K + 6 * (int(np.ceil(np.log2(max(n, 2)))) + 2) + 16

    adj_f = adj.reshape((-1, n, n))
    B = adj_f.shape[0]
    def flat(a):
        return np.asarray(a, np.float64).reshape(
            (-1,) + a.shape[len(batch_shape):])

    eoff_f, occ_f, prop_f = (flat(np.asarray(edge_off)), flat(np.asarray(occ)),
                             flat(np.asarray(prop)))

    # pad predecessor lists to the max in-degree across the batch
    with span("tables"):
        dmax = int(adj_f.sum(axis=1).max())
        pred = np.zeros((B, n, dmax), dtype=np.int32)
        pred_cost = np.full((B, n, dmax), BIG, dtype=np.float64)
        pred_mask = np.zeros((B, n, dmax), dtype=bool)
        for b in range(B):
            for v in range(n):
                us = np.flatnonzero(adj_f[b, :, v])
                pred[b, v, :len(us)] = us
                pred_cost[b, v, :len(us)] = (eoff_f[b, us, v]
                                             + prop_f[b, us, v])
                pred_mask[b, v, :len(us)] = True

    with span("dispatch"), enable_x64():
        fn = _compiled_reliable(n, K, dmax, max_iters, True, engine)
        C, tstart, iters, resid = fn(pred, pred_cost, pred_mask, occ_f)
    C, resid = np.asarray(C), np.asarray(resid)
    # insurance: the warm-started solve must agree with the trustworthy cold
    # prefix and be fully resolved; otherwise redo the whole batch cold
    if (resid > 1e-9).any() or not np.isfinite(C).all() or (C > BIG / 2).any():
        with span("resolve"):
            with span("dispatch"), enable_x64():
                fn = _compiled_reliable(n, K, dmax, 8 * max_iters, False,
                                        engine)
                C, tstart, iters, _ = fn(pred, pred_cost, pred_mask, occ_f)
            C = np.asarray(C)
    C = C.reshape(batch_shape + (K, n))
    tstart = np.asarray(tstart).reshape(batch_shape + (K, n))
    with span("sync"):
        iterations = int(np.max(iters))
    return RoundTimes(completion=C, start=tstart, iterations=iterations)


@functools.lru_cache(maxsize=64)
def _compiled_reliable(n: int, K: int, dmax: int, max_iters: int, warm: bool,
                       engine: str = "vec"):
    jax, jnp = _jax()
    from ..kernels.compat import enable_x64

    use_pallas = engine == "pallas"

    with enable_x64():
        def solve(Kc, pred, pred_cost, pred_mask, occ, ts0, iters_cap,
                  A0=None, inst0=None, pallas_tables=None):
            if A0 is None:
                A0 = jnp.full((Kc, n, n), BIG, jnp.float64)
            inst0 = A0 if inst0 is None else inst0
            t0 = jnp.zeros((), jnp.float64)

            # Jacobi sweeps to the fixpoint, then one last sweep from the
            # converged state that yields C.  The last sweep runs as the
            # loop's final trip (``done``), so the step body is traced and
            # compiled once per solve: it keeps the loop's entry times and
            # iteration count, and takes the sweep's A1, inst and C.
            def cond(state):
                return ~state[-1]

            def body(state):
                A1, inst, ts, it, delta, _C, _done = state
                final = (it >= iters_cap) | (delta <= _EPS)
                A1n, instn, tsn, C = _reliable_step(
                    jax, jnp, A1, inst, ts, pred, pred_cost, pred_mask, occ,
                    t0, pallas_tables)
                delta = jnp.maximum(
                    jnp.max(jnp.abs(jnp.clip(A1n, 0, BIG) - jnp.clip(A1, 0, BIG))),
                    jnp.max(jnp.abs(jnp.clip(instn, 0, BIG) - jnp.clip(inst, 0, BIG))))
                return (A1n, instn, jnp.where(final, ts, tsn),
                        it + jnp.where(final, 0, 1).astype(jnp.int32), delta,
                        C, final)

            C0 = jnp.zeros((Kc, n), jnp.float64)
            A1, inst, ts, it, _, C, _ = jax.lax.while_loop(
                cond, body, (A0, inst0, ts0, jnp.int32(0), jnp.float64(BIG),
                             C0, jnp.bool_(False)))
            return C, ts, it, A1, inst

        def single(pred, pred_cost, pred_mask, occ):
            pallas_tables = None
            if use_pallas:
                # dense G_R edge costs (inf off-edge), stacked twice along
                # the contraction axis — once per forward event kind; gather
                # rows with BIG padding (in-degree < dmax) are flagged so
                # the dense min gets the identical BIG cap
                v_col = jnp.arange(n)[:, None]
                dense = jnp.full((n, n), jnp.inf, jnp.float64).at[
                    pred, v_col].min(
                        jnp.where(pred_mask, pred_cost, jnp.inf))
                pallas_tables = (jnp.concatenate([dense, dense], axis=0),
                                 ~jnp.all(pred_mask, axis=-1))
            # cold Jacobi resolves rounds strictly one-by-one (~settle
            # iterations each).  Warm-start: solve a short prefix cold, then
            # extrapolate round entries by the steady-state period so all K
            # rounds settle in parallel; the final while_loop still runs to
            # the exact joint fixpoint, and the caller cross-checks the
            # result against the cold prefix (resid) before trusting it.
            K1 = min(3, K)
            ts0 = jnp.concatenate(
                [jnp.zeros((1, n)), jnp.full((K1 - 1, n), BIG)], 0)
            if not warm or K1 == K:
                ts_cold = jnp.concatenate(
                    [jnp.zeros((1, n)), jnp.full((K - 1, n), BIG)], 0)
                C, ts, it, _A, _i = solve(K, pred, pred_cost, pred_mask, occ,
                                          ts_cold, jnp.int32(max_iters),
                                          pallas_tables=pallas_tables)
                return C, ts, it, jnp.float64(0.0)
            C1, _ts1, it1, A1_1, inst1 = solve(K1, pred, pred_cost, pred_mask,
                                               occ, ts0, jnp.int32(max_iters),
                                               pallas_tables=pallas_tables)
            # extrapolate entry times AND arrival matrices by the per-server
            # steady-state period so late rounds start near their fixpoint
            period = C1[-1] - C1[-2]                       # per-server [n]
            k_off = jnp.arange(1, K - K1 + 1, dtype=jnp.float64)[:, None, None]
            ts_warm = jnp.concatenate(
                [jnp.zeros((1, n)), C1[:-1],
                 C1[-1][None]
                 + jnp.arange(K - K1, dtype=jnp.float64)[:, None]
                 * period[None]], 0)
            shift = k_off * period[None, None, :]          # [K-K1, 1, n]
            A_warm = jnp.concatenate([A1_1, A1_1[-1][None] + shift], 0)
            inst_warm = jnp.concatenate([inst1, inst1[-1][None] + shift], 0)
            C, ts, it2, _A, _i = solve(K, pred, pred_cost, pred_mask, occ,
                                       ts_warm, jnp.int32(max_iters),
                                       A0=A_warm, inst0=inst_warm,
                                       pallas_tables=pallas_tables)
            resid = jnp.max(jnp.abs(C[:K1] - C1))
            return C, ts, it1 + it2, resid

        # the program's name is what compile logs and profiles report
        single.__name__ = (f"vecsim_reliable_n{n}_d{dmax}_k{K}_"
                           f"{'warm' if warm else 'cold'}_{engine}")
        return jax.jit(jax.vmap(single))


# ---------------------------------------------------------------------------
# metrics: replicate repro.sim.runner.Metrics summaries from round times
# ---------------------------------------------------------------------------

def summarize(times: RoundTimes, *, mode: str, n: int, batch: int,
              window: Tuple[int, int] = (10, 110)) -> dict:
    """Per-config summary matching the event engine's ``Metrics`` semantics.

    Deliver events: AllGather / AllConcur deliver round k at its completion;
    AllConcur+ (DUAL) delivers round k-1 when round k completes (and round 1,
    the first ``|>`` round, delivers nothing).  Latency is A-broadcast (round
    entry) to own-message A-delivery, as in ``Metrics.on_deliver_msg``.
    """
    C, ts = times.completion, times.start        # [..., K, n]
    K = C.shape[-2]
    lo_mult, hi_mult = window

    if mode == "allconcur+":
        deliver = C[..., 1:, :]                  # round k-1 delivered at C_k
        lat = C[..., 1:, :] - ts[..., :-1, :]    # abcast at entry of k-1
    else:
        deliver = C
        lat = C - ts
    median_latency = np.median(lat, axis=(-2, -1))

    # window(): per server, accumulate n msgs per deliver event; t1/t2 are the
    # max over servers of the first event reaching lo/hi * n messages.
    nev = deliver.shape[-2]
    lo_ev, hi_ev = lo_mult, hi_mult              # acc after j events = j * n
    t1 = np.max(deliver[..., lo_ev - 1, :], axis=-1) if lo_ev <= nev \
        else np.zeros(C.shape[:-2])
    if hi_ev <= nev:
        t2 = np.max(deliver[..., hi_ev - 1, :], axis=-1)
    else:
        t2 = np.max(deliver[..., -1, :], axis=-1)    # fallback: last event
    span = t2 - t1
    with np.errstate(invalid="ignore", divide="ignore"):
        in_win = ((deliver > t1[..., None, None])
                  & (deliver <= t2[..., None, None])).sum(axis=-2)
        msgs = in_win * n * batch
        thr = np.where(span > 0, msgs.mean(axis=-1) / np.where(span > 0, span, 1.0),
                       np.nan)
    return {
        "median_latency": median_latency,
        "throughput": thr,
        "t_window": (t1, t2),
        "round_period": np.median(np.diff(np.max(C, axis=-1), axis=-1), axis=-1),
        "completion": C,
    }
