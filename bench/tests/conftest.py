import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def quiet_jax(monkeypatch):
    """Keep a test run's JAX state as it was: no persistent compile cache,
    no compile listeners left registered."""
    from bench.harness import device
    monkeypatch.setattr(device, "set_compile_cache", lambda: "")
    monkeypatch.setattr(device.CompileClock, "install", lambda self: self)


def small_cell(name: str):
    """The cell ``name`` cut for runs on the CPU: a sweep at n = 8, a client
    population 125-fold smaller (same load per server, horizon from the same
    rule)."""
    from bench.harness import spec
    cell = spec.cell(name)
    if cell.traffic["kind"] == "sweep":
        cell.traffic = dict(cell.traffic, n=8, reference_sample=12)
    else:
        c = cell.config
        cell.config = dict(c, clients=c["clients"] // 125,
                           rate=c["rate"] * 125)
        cps = cell.config["clients"] // c["n"]
        q = cell.traffic["requests_per_client"]
        base = int(cps * q / (c["util"] * c["batch_max"] / 2))
        cell.traffic = dict(cell.traffic, rounds=cell.traffic[
            "horizon_mean_spans"] * base + 64)
    return cell
