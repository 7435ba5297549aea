"""``bench/run.py`` refuses to run without a TPU and never falls back."""
import json
import os
import subprocess
import sys

import pytest

from bench.harness import spec


def test_run_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", "sweep_n64", "--seed", str(2 ** 31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        try:
            row = json.loads(line)
        except ValueError:
            continue
        assert "correct" not in row


def test_traced_run_without_device_events_reports_none(monkeypatch,
                                                      quiet_jax):
    import time

    from bench.harness import runner
    from bench.tests.conftest import small_cell
    cell = small_cell("sweep_n64")
    monkeypatch.setattr(spec, "cell", lambda _name: cell)
    r = runner.run("sweep_n64", 7, 0.2, True, started=time.perf_counter(),
                   require_chip=False)
    assert r["correct"] is True
    assert r["metrics"] == {}            # the CPU trace has no TPU plane
    assert r["device"]["busy_s"] == 0.0
    assert list(r)[-1] == "compared"


@pytest.mark.parametrize("ahead", [1, 3])
def test_window_counts_every_started_answer_in_order(ahead):
    import threading
    import time

    from bench.harness import runner

    class Slow:
        def __init__(self):
            self.lock = threading.Lock()
            self.live = self.most = 0
            self.starts = []

        def answer(self, i):
            with self.lock:
                self.starts.append(time.perf_counter())
                self.live += 1
                self.most = max(self.most, self.live)
            time.sleep(0.1)
            with self.lock:
                self.live -= 1
            return i + 1, ("record", i)

    drv = Slow()
    records, times, work, window_s = runner._window(drv, 0.5, ahead)
    n = len(records)
    assert records == [("record", i) for i in range(n)]
    assert work == n * (n + 1) // 2 and len(times) == n
    assert drv.most == ahead and len(drv.starts) == n
    # nothing starts once the time is up: at most ``ahead`` per 0.1 s
    assert ahead <= n <= ahead * (0.5 / 0.1 + 1)
    assert window_s >= 0.5 and all(t >= 0.1 for t in times)
