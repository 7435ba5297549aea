"""A whole run on the CPU at a small size: sound, it is correct; with the
timed path broken underneath, or in float32 (the control), it is not.

Each fault is planted in the program the window drives, never in the
reference: an answer altered where it is produced, half of the work left
out and filled from the other half, and a step that returns its state
unchanged.  One chip runs each cell, so no exchange between chips exists
to leave out.
"""
import time

import numpy as np
import pytest

from bench import control
from bench.harness import runner, spec
from bench.tests.conftest import small_cell

SEED = 2 ** 31 + 4242


def _run(monkeypatch, name, seconds=0.5):
    cell = small_cell(name)
    monkeypatch.setattr(spec, "cell", lambda _name: cell)
    return runner.run(name, SEED, seconds, False,
                      started=time.perf_counter(), require_chip=False)


def _altered_sweep_answer(monkeypatch):
    from repro.vecsim import engine
    real = engine.summarize

    def summarize(times, **kw):
        out = dict(real(times, **kw))
        out["median_latency"] = out["median_latency"] * (1 + 1e-9)
        return out
    monkeypatch.setattr(engine, "summarize", summarize)


def _half_of_sweep(monkeypatch):
    from repro.vecsim import engine

    def halved(real):
        def run(*tables, **kw):
            half = (len(tables[0]) + 1) // 2
            rt = real(*(t[:half] for t in tables), **kw)
            take = np.arange(len(tables[0])) % half
            return engine.RoundTimes(completion=rt.completion[take],
                                     start=rt.start[take],
                                     iterations=rt.iterations)
        return run
    for name in ("run_reliable", "run_unreliable"):
        monkeypatch.setattr(engine, name, halved(getattr(engine, name)))


def _stuck_sweep(monkeypatch):
    from repro.vecsim import engine
    real = engine.run_unreliable

    def run_unreliable(*a, **kw):
        rt = real(*a, **kw)
        first = rt.completion[..., :1, :]
        return engine.RoundTimes(
            completion=np.broadcast_to(first, rt.completion.shape)
            + np.arange(rt.completion.shape[-2])[:, None] * 0.0,
            start=rt.start, iterations=rt.iterations)
    monkeypatch.setattr(engine, "run_unreliable", run_unreliable)


def _altered_round(monkeypatch):
    from repro.vecsim import clients
    real = clients._assign_rounds

    def assign(*a, **kw):
        out = np.array(real(*a, **kw))
        out[..., 0, :] += 1
        return out
    monkeypatch.setattr(clients, "_assign_rounds", assign)


def _half_of_servers(monkeypatch):
    from repro.vecsim import clients
    real = clients._assign_rounds

    def assign(entry, s, **kw):
        half = s.shape[0] // 2
        out = np.asarray(real(entry[..., :half, :] if entry.shape[-2] > 1
                              else entry, s[:half], **kw))
        return np.concatenate([out, out], axis=-2)
    monkeypatch.setattr(clients, "_assign_rounds", assign)


def _stuck_capacity(monkeypatch):
    from repro.vecsim import clients
    real = clients._assign_rounds

    def assign(*a, **kw):
        out = np.array(real(*a, **kw))
        return np.zeros_like(out)
    monkeypatch.setattr(clients, "_assign_rounds", assign)


def _altered_timeline(monkeypatch):
    from repro.vecsim import failures
    real = failures.monte_carlo_times

    def mct(*a, **kw):
        t = real(*a, **kw)
        return failures.MonteCarloTimes(entry=t.entry, deliver=t.deliver
                                        * (1 + 1e-5), crashes=t.crashes,
                                        total_time=t.total_time)
    monkeypatch.setattr(failures, "monte_carlo_times", mct)


FAULTS = {
    "sweep_n64": [_altered_sweep_answer, _half_of_sweep, _stuck_sweep],
    "clients_open_loop": [_altered_round, _half_of_servers, _stuck_capacity],
    "clients_mc_crash": [_altered_timeline, _half_of_servers,
                         _stuck_capacity],
}


@pytest.mark.parametrize("name", list(FAULTS))
def test_sound_run_is_correct(monkeypatch, quiet_jax, name):
    r = _run(monkeypatch, name)
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert list(r)[-1] == "compared"
    assert set(r["metrics"]) == {m["name"] for m in
                                 small_cell(name).end_to_end}


@pytest.mark.parametrize("name,fault", [(n, f) for n, fs in FAULTS.items()
                                        for f in fs],
                         ids=lambda x: getattr(x, "__name__", x))
def test_broken_path_is_not_correct(monkeypatch, quiet_jax, name, fault):
    fault(monkeypatch)
    r = _run(monkeypatch, name)
    assert r["correct"] is False, r["compared"]


@pytest.mark.parametrize("name", list(FAULTS))
def test_float32_control_fails(quiet_jax, name):
    rows = control.readings(small_cell(name), [SEED], 2, "float32")
    assert not rows[0]["within_limits"], rows
