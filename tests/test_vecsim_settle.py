"""G_U settle-and-extend: a long horizon relaxes a prefix on the device and
extends it by its steady period; the result must stay the full scan's."""
from contextlib import contextmanager

import numpy as np
import pytest

from repro.kernels.compat import enable_x64
from repro.vecsim import unreliable_tables
from repro.vecsim import engine as vec_engine
from repro.vecsim.clients import smr_round_times

P = vec_engine.SETTLE_PREFIX


def _tables(mode, n, network):
    t = unreliable_tables(n, network=network, mode=mode)
    return t.parent, t.send_off, t.occ, t.prop


def _full_scan(monkeypatch, tables, rounds, **kw):
    with monkeypatch.context() as m:
        m.setattr(vec_engine, "SETTLE_PREFIX", rounds)
        return vec_engine.run_unreliable(*tables, rounds=rounds, **kw)


def _rel(a, b):
    return float(np.max(np.abs(a - b) / np.abs(b).clip(1e-300)))


@pytest.mark.parametrize("network", ["sdc", "mdc"])
@pytest.mark.parametrize("n", [8, 16])
@pytest.mark.parametrize("mode", ["allconcur+", "allgather"])
def test_long_horizon_matches_full_scan(monkeypatch, mode, n, network):
    K = 4 * P
    tables = _tables(mode, n, network)
    got = vec_engine.run_unreliable(*tables, rounds=K)
    want = _full_scan(monkeypatch, tables, K)
    assert got.completion.shape == got.start.shape == (K, n)
    assert want.relaxed == K
    if got.relaxed == P:
        assert _rel(got.start, want.start) <= 1e-12
        assert _rel(got.completion, want.completion) <= 1e-12
    else:
        # the mdc trees' period spans several rounds: the full scan answers
        assert got.relaxed == K
        np.testing.assert_array_equal(got.start, want.start)
        np.testing.assert_array_equal(got.completion, want.completion)
    assert got.iterations == want.iterations
    if network == "sdc":
        assert got.relaxed == P


def test_open_loop_shape_engages():
    K = 78184
    rt = smr_round_times("allconcur+", 8, reqs_per_round=64, rounds=K,
                         network="sdc", value_size=16)
    assert rt.relaxed == P
    assert rt.start.shape == rt.completion.shape == (K, 8)
    assert (np.diff(rt.start, axis=0) > 0).all()
    head = smr_round_times("allconcur+", 8, reqs_per_round=64, rounds=P,
                           network="sdc", value_size=16)
    np.testing.assert_array_equal(rt.start[:P], head.start)
    np.testing.assert_array_equal(rt.completion[:P], head.completion)


def test_batched_prefix_extends_each_member(monkeypatch):
    stacked = [np.stack([a, b]) for a, b in
               zip(_tables("allconcur+", 8, "sdc"),
                   _tables("allgather", 8, "sdc"))]
    K = P + 100
    got = vec_engine.run_unreliable(*stacked, rounds=K)
    want = _full_scan(monkeypatch, stacked, K)
    assert got.relaxed == P and got.start.shape == (2, K, 8)
    assert _rel(got.start, want.start) <= 1e-12
    assert _rel(got.completion, want.completion) <= 1e-12


@pytest.mark.parametrize("part", ["entry", "free"])
def test_period_check_covers_the_whole_carry(part):
    # a synthetic settled prefix: every server steps by lam, offsets differ
    B, n, lam = 2, 4, 2.5e-4
    W = vec_engine.SETTLE_WINDOW
    k = np.arange(P, dtype=np.float64)[None, :, None]
    off = np.arange(B * n, dtype=np.float64).reshape(B, 1, n) * 1e-5
    ts = off + k * lam
    C = ts + lam
    free_end = ts + 0.7 * lam
    period = vec_engine._settled_period(ts, C, free_end)
    np.testing.assert_allclose(period, lam, rtol=1e-12)
    # one component of one member, inside the window: the check fails
    bad_ts, bad_free = ts.copy(), free_end.copy()
    (bad_ts if part == "entry" else bad_free)[1, P - W // 2, n - 1] += 1e-9 * lam
    assert vec_engine._settled_period(bad_ts, bad_ts + lam, bad_free) is None
    # the same step before the window is a transient: the check holds
    early_ts, early_free = ts.copy(), free_end.copy()
    (early_ts if part == "entry" else early_free)[1, :P - W - 2, n - 1] -= lam
    assert vec_engine._settled_period(early_ts, early_ts + lam,
                                      early_free) is not None


def test_pallas_engine_extends_bit_for_bit_as_vec():
    tables = _tables("allconcur+", 8, "sdc")
    a = vec_engine.run_unreliable(*tables, rounds=P + 44, engine="pallas")
    b = vec_engine.run_unreliable(*tables, rounds=P + 44)
    assert a.relaxed == b.relaxed == P
    np.testing.assert_array_equal(a.start, b.start)
    np.testing.assert_array_equal(a.completion, b.completion)


@contextmanager
def _recorded_spans(monkeypatch):
    names = []
    real = vec_engine.span

    def span(name):
        names.append(name)
        return real(name)
    with monkeypatch.context() as m:
        m.setattr(vec_engine, "span", span)
        yield names


@pytest.mark.parametrize("network", ["sdc", "mdc"])
def test_unsettled_prefix_falls_back_to_full_scan(monkeypatch, network):
    # sdc and uniform trees step by one period from the first round on; the
    # mdc trees repeat every 4 rounds, so no prefix passes the check, and a
    # check that cannot pass stands in for a transient longer than P on sdc
    tables = _tables("allconcur+", 8, network)
    K = 64
    want = _full_scan(monkeypatch, tables, K)
    monkeypatch.setattr(vec_engine, "SETTLE_PREFIX", 16)
    monkeypatch.setattr(vec_engine, "SETTLE_WINDOW", 8)
    if network == "sdc":
        monkeypatch.setattr(vec_engine, "SETTLE_TOL", -1.0)
    with _recorded_spans(monkeypatch) as names:
        got = vec_engine.run_unreliable(*tables, rounds=K)
    assert got.relaxed == K
    np.testing.assert_array_equal(got.start, want.start)
    np.testing.assert_array_equal(got.completion, want.completion)
    assert got.iterations == want.iterations
    assert names == ["dispatch", "rescan", "dispatch", "sync"]


def test_settled_prefix_extends_under_its_span(monkeypatch):
    tables = _tables("allconcur+", 8, "sdc")
    with _recorded_spans(monkeypatch) as names:
        got = vec_engine.run_unreliable(*tables, rounds=2 * P)
    assert got.relaxed == P
    assert names == ["dispatch", "extend", "sync"]


@pytest.mark.parametrize("rounds", [12, P])
def test_short_horizon_runs_the_full_program_unchanged(monkeypatch, rounds):
    tables = _tables("allconcur+", 8, "sdc")
    calls = []
    real = vec_engine._compiled_unreliable

    def compiled(*a, **kw):
        calls.append((a, kw))
        return real(*a, **kw)
    monkeypatch.setattr(vec_engine, "_compiled_unreliable", compiled)
    got = vec_engine.run_unreliable(*tables, rounds=rounds)
    assert len(calls) == 1
    (n, K, max_iters, engine), kw = calls[0]
    assert (n, K, engine, kw) == (8, rounds, "vec", {})
    assert real(*calls[0][0]).__name__ == f"vecsim_unreliable_n8_k{rounds}_vec"
    assert got.relaxed == rounds
    # the prefix program relaxes the same rounds, bit for bit
    parent, send_off, occ, prop = (np.asarray(a)[None] for a in tables)
    with enable_x64():
        C, ts, free_end, _it = real(n, K, max_iters, engine,
                                    free_times=True)(
            parent.astype(np.int32), send_off, occ, prop)
    np.testing.assert_array_equal(np.asarray(C)[0], got.completion)
    np.testing.assert_array_equal(np.asarray(ts)[0], got.start)
    assert np.asarray(free_end).shape == (1, rounds, 8)


def test_round_times_default_relaxed():
    rt = vec_engine.RoundTimes(completion=np.zeros((2, 3)),
                               start=np.zeros((2, 3)), iterations=1)
    assert rt.relaxed == 0
