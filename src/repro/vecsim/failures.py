"""Monte-Carlo robustness estimates (paper Fig. 6 style), batched.

The event engine can afford a few dozen sampled crash schedules per study;
here thousands of schedules are evaluated in one vmapped jax program by
*splicing* analytically-known round segments instead of replaying events:

- failure-free segments advance in G_U rounds of length ``du`` (measured by
  :mod:`repro.vecsim.engine` for the exact deployment);
- a crash inside a round wastes the elapsed unreliable prefix, costs the
  failure-detector timeout ``delta_to``, and is repaired by two G_R rounds of
  length ``dr`` (the rolled-back round rerun reliably — transition T_UR — and
  the transitional reliable round T_RR), after which unreliable rounds
  resume with one server fewer.

Per-schedule outputs (throughput, mean delivered latency) follow the paper's
aggregation: AllConcur+ messages normally see ~2 du (A-delivery lags one
round); messages of a crashed round are delivered at the end of the first
recovery round.  Passing per-membership ``du_by_f`` / ``dr_by_f`` (round
lengths after f crashes, from the engine) makes the splice membership-aware.

**Eon transitions (§III-I).**  ``eon_round=k`` splices a mid-run topology
swap: round ``k`` becomes the transitional *reliable* round (length ``dr``
of the pre-flip tables, messages delivered at its completion), and every
later round draws from the post-flip tables ``du2_by_f`` / ``dr2_by_f``
(round lengths measured on the new dual digraphs, e.g. after an
``add_server``) with post-flip membership size ``n2``.  Monte-Carlo
robustness sweeps therefore cover reconfiguration the same way they cover
crash schedules — a crash sampled inside or after the transition composes
with the swapped cost tables.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np

from .spans import span

BIG = 1e12


@dataclass(frozen=True)
class MonteCarloResult:
    throughput: np.ndarray      # [S] txn / s / server
    mean_latency: np.ndarray    # [S] seconds
    crashes: np.ndarray         # [S] crashes that landed inside the horizon
    total_time: np.ndarray      # [S] seconds to deliver all rounds

    def summary(self) -> dict:
        def q(a, p):
            return float(np.percentile(a, p))

        return {
            "throughput_mean": float(self.throughput.mean()),
            "throughput_p5": q(self.throughput, 5),
            "throughput_p95": q(self.throughput, 95),
            "latency_mean_us": float(self.mean_latency.mean()) * 1e6,
            "latency_p95_us": q(self.mean_latency, 95) * 1e6,
            "crashes_mean": float(self.crashes.mean()),
            "schedules": int(self.throughput.shape[0]),
        }


@dataclass(frozen=True)
class MonteCarloTimes:
    """Per-round spliced timelines (one row per sampled schedule).

    ``entry[s, k]`` is the abcast time of round ``k`` under schedule ``s``
    and ``deliver[s, k]`` the A-delivery time of that round's payload (for
    AllConcur+ the one-round delivery lag and crash-recovery splices are
    already folded in, exactly as :func:`monte_carlo` aggregates them).
    The vectorized client layer replays arrival streams against these
    timelines to turn Fig.-6-style robustness sweeps into client-perceived
    latency distributions.
    """
    entry: np.ndarray           # [S, R] round abcast times
    deliver: np.ndarray         # [S, R] payload A-delivery times
    crashes: np.ndarray         # [S] crashes inside the horizon
    total_time: np.ndarray      # [S] seconds to deliver all rounds


def monte_carlo(du: float, dr: float, *, n: int, batch: int,
                mtbf: float, fd_timeout: float = 10e-3,
                rounds: int = 200, n_schedules: int = 2048, seed: int = 0,
                max_failures: int = 4,
                du_by_f: Optional[Sequence[float]] = None,
                dr_by_f: Optional[Sequence[float]] = None,
                eon_round: Optional[int] = None,
                du2_by_f: Optional[Sequence[float]] = None,
                dr2_by_f: Optional[Sequence[float]] = None,
                n2: Optional[int] = None) -> MonteCarloResult:
    """Estimate AllConcur+ performance under sampled crash times.

    ``mtbf`` is the mean time between crashes across the deployment (the
    paper's Fig. 6 x-axis is the equivalent "failure-free rounds between
    failures" lambda = mtbf / du).  Crash times are i.i.d. exponential gaps;
    at most ``max_failures`` crashes are spliced per schedule (f <= d - 1
    keeps G_R connected, matching the protocol's resilience assumption).

    ``eon_round`` (with ``du2_by_f``/``dr2_by_f``/``n2``) splices an eon
    transition: see the module docstring.
    """
    thr, lat, crashes, total, _entry, _deliver = _mc_run(
        du, dr, n=n, batch=batch, mtbf=mtbf, fd_timeout=fd_timeout,
        rounds=rounds, n_schedules=n_schedules, seed=seed,
        max_failures=max_failures, du_by_f=du_by_f, dr_by_f=dr_by_f,
        eon_round=eon_round, du2_by_f=du2_by_f, dr2_by_f=dr2_by_f, n2=n2)
    return MonteCarloResult(throughput=thr, mean_latency=lat,
                            crashes=crashes, total_time=total)


def monte_carlo_times(du: float, dr: float, *, n: int, batch: int,
                      mtbf: float, fd_timeout: float = 10e-3,
                      rounds: int = 200, n_schedules: int = 2048,
                      seed: int = 0, max_failures: int = 4,
                      du_by_f: Optional[Sequence[float]] = None,
                      dr_by_f: Optional[Sequence[float]] = None,
                      eon_round: Optional[int] = None,
                      du2_by_f: Optional[Sequence[float]] = None,
                      dr2_by_f: Optional[Sequence[float]] = None,
                      n2: Optional[int] = None) -> MonteCarloTimes:
    """Like :func:`monte_carlo` but export the spliced per-round timelines
    (abcast + A-delivery time per round per schedule) instead of aggregate
    throughput/latency — the input the vectorized client layer needs to
    compute client-perceived percentiles under crash/eon-flip schedules.
    """
    _thr, _lat, crashes, total, entry, deliver = _mc_run(
        du, dr, n=n, batch=batch, mtbf=mtbf, fd_timeout=fd_timeout,
        rounds=rounds, n_schedules=n_schedules, seed=seed,
        max_failures=max_failures, du_by_f=du_by_f, dr_by_f=dr_by_f,
        eon_round=eon_round, du2_by_f=du2_by_f, dr2_by_f=dr2_by_f, n2=n2)
    return MonteCarloTimes(entry=entry, deliver=deliver,
                           crashes=crashes, total_time=total)


def _mc_run(du: float, dr: float, *, n: int, batch: int, mtbf: float,
            fd_timeout: float, rounds: int, n_schedules: int, seed: int,
            max_failures: int,
            du_by_f: Optional[Sequence[float]],
            dr_by_f: Optional[Sequence[float]],
            eon_round: Optional[int],
            du2_by_f: Optional[Sequence[float]],
            dr2_by_f: Optional[Sequence[float]],
            n2: Optional[int]):
    import jax
    import jax.numpy as jnp
    from ..kernels.compat import enable_x64

    du_f = np.asarray(du_by_f if du_by_f is not None
                      else [du] * (max_failures + 1), dtype=np.float64)
    dr_f = np.asarray(dr_by_f if dr_by_f is not None
                      else [dr] * (max_failures + 1), dtype=np.float64)
    if len(du_f) != max_failures + 1 or len(dr_f) != max_failures + 1:
        raise ValueError("du_by_f/dr_by_f must have max_failures+1 entries")
    du2_f = np.asarray(du2_by_f if du2_by_f is not None else du_f,
                       dtype=np.float64)
    dr2_f = np.asarray(dr2_by_f if dr2_by_f is not None else dr_f,
                       dtype=np.float64)
    if len(du2_f) != max_failures + 1 or len(dr2_f) != max_failures + 1:
        raise ValueError("du2_by_f/dr2_by_f must have max_failures+1 entries")
    if eon_round is not None and not 0 <= eon_round < rounds:
        raise ValueError(f"eon_round {eon_round} outside [0, {rounds})")
    # a sentinel past the horizon disables the splice without a branch
    eon_idx = rounds + 1 if eon_round is None else int(eon_round)
    n_post = n if n2 is None else int(n2)

    # the crash draws, the program (built anew on every call) and its launch
    with span("dispatch"), enable_x64():
        key = jax.random.PRNGKey(seed)
        gaps = jax.random.exponential(key, (n_schedules, max_failures),
                                      dtype=jnp.float64) * mtbf
        crash_times = jnp.cumsum(gaps, axis=1)

        du_a = jnp.asarray(du_f)
        dr_a = jnp.asarray(dr_f)
        du2_a = jnp.asarray(du2_f)
        dr2_a = jnp.asarray(dr2_f)

        def one_schedule(crashes):
            def step(state, idx):
                # the clock is a compensated sum (t + tc): over a horizon of
                # 10^4-10^5 rounds a plain float64 sum of round lengths
                # drifts by ~1e-12 relative (and ~1e-11 in a TPU's emulated
                # float64), past the timelines' contract
                t, tc, ptr, f, lat_sum, msg_sum = state
                now = t + tc
                post = idx > eon_idx           # new eon's dual digraphs
                at_eon = idx == eon_idx        # the transitional round
                du_k = jnp.where(post, du2_a[f], du_a[f])
                dr_k = jnp.where(post, dr2_a[f], dr_a[f])
                # the transitional round runs reliably on the *old* G_R
                # (§III-I: the swap applies after its completion)
                dur = jnp.where(at_eon, dr_a[f], du_k)
                nxt = jnp.where(ptr < max_failures,
                                crashes[jnp.minimum(ptr, max_failures - 1)],
                                BIG)
                crashed = nxt < now + dur
                # crash: wasted prefix + detection + two reliable rounds;
                # the round's messages deliver at the end of the first one.
                # A crash sampled inside the previous recovery window (nxt
                # < now) is detected once that recovery ends: clamp to the
                # round start so latency/duration stay positive.
                rec1 = (jnp.maximum(nxt, now) - now) + fd_timeout + dr_k
                step_len = jnp.where(crashed, rec1 + dr_k, dur)
                # reliable rounds deliver at completion (1x), unreliable
                # A-delivery lags one round (2x)
                lat = jnp.where(crashed, rec1,
                                jnp.where(at_eon, dur, 2.0 * du_k))
                alive = jnp.where(post, n_post, n) - f
                new_f = jnp.minimum(f + crashed.astype(jnp.int32),
                                    max_failures)
                t_next = t + step_len
                tc_next = tc + jnp.where(
                    jnp.abs(t) >= jnp.abs(step_len),
                    (t - t_next) + step_len, (step_len - t_next) + t)
                return ((t_next, tc_next, ptr + crashed.astype(jnp.int32),
                         new_f, lat_sum + lat * alive, msg_sum + alive),
                        (now, now + lat))

            init = (jnp.float64(0.0), jnp.float64(0.0), jnp.int32(0),
                    jnp.int32(0), jnp.float64(0.0), jnp.int64(0))
            (t, tc, ptr, f, lat_sum, msg_sum), (entry, deliver) = \
                jax.lax.scan(step, init, jnp.arange(rounds))
            t = t + tc
            thr = msg_sum * batch / t            # txn / s / server
            return thr, lat_sum / msg_sum, ptr, t, entry, deliver

        fn = jax.jit(jax.vmap(one_schedule))
        thr, lat, crashes, total, entry, deliver = fn(crash_times)

    return (np.asarray(thr), np.asarray(lat), np.asarray(crashes),
            np.asarray(total), np.asarray(entry), np.asarray(deliver))
