"""The reduction from a profiler trace to busy time, program times and
idle gaps."""
import json
import os

import pytest

from bench.harness import spec, trace

FIXTURE = os.path.join(spec.BENCH_DIR, "tests", "fixtures",
                       "sweep_n8_trace_events.json")


def _metric(name, reduced):
    return spec.cell("sweep_n64").metric_reader(name).read(reduced)


def test_reduce_synthetic_window():
    ms = 1e6
    events = {
        "devices": {"/device:TPU:0": [
            ["jit_vecsim_a", 1 * ms, 2 * ms],       # 1-3
            ["jit_vecsim_a", 2 * ms, 2 * ms],       # 2-4, overlaps
            ["jit_pipeline", 6 * ms, 1 * ms],       # 6-7
            ["jit_other", 20 * ms, 5 * ms]]},       # outside the window
        "host": [["answer", 0, 10 * ms], ["sweep", 4 * ms, 3 * ms],
                 ["server_streams", 7.5 * ms, 2.5 * ms],
                 ["unrelated", 0, 10 * ms]]}
    r = trace.reduce(events)
    assert r.window_s == pytest.approx(0.010)
    assert r.busy_s == pytest.approx(0.004)           # 1-4 and 6-7
    assert r.modules == pytest.approx({"jit_vecsim_a": 0.004,
                                       "jit_pipeline": 0.001})
    # gaps 0-1, 4-6 (inside "sweep"), 7-10 (midpoint inside server_streams)
    assert r.gaps == [["server_streams", pytest.approx(0.003)],
                      ["sweep", pytest.approx(0.002)],
                      ["unrelated", pytest.approx(0.001)]]
    assert _metric("device_idle.sweep", r) == pytest.approx(60.0)
    assert _metric("engine_ms.sweep", r) == pytest.approx(4.0)
    b = r.breakdown()
    assert b["device_ops"][0] == ["jit_vecsim_a", pytest.approx(0.004)]
    assert len(b["idle_gaps"]) <= 10


def test_no_answer_or_no_device_reads_nothing():
    assert trace.reduce({"devices": {}, "host": [["answer", 0, 1]]}) is None
    assert trace.reduce({"devices": {"/device:TPU:0": []},
                         "host": []}) is None
    assert _metric("device_idle.sweep", None) is None
    assert _metric("engine_ms.sweep", None) is None


def test_recorded_chip_trace():
    with open(FIXTURE) as f:
        fixture = json.load(f)
    r = trace.reduce(fixture["events"])
    want = fixture["reduced"]
    assert r.answers == want["answers"]
    assert r.window_s == pytest.approx(want["window_s"], rel=1e-12)
    assert r.busy_s == pytest.approx(want["busy_s"], rel=1e-12)
    assert r.modules == pytest.approx(want["modules"], rel=1e-12)
    assert 0 < r.busy_s < r.window_s
    assert any(k.startswith("jit_vecsim_") for k in r.modules)
