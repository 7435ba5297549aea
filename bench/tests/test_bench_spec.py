"""Every cell of BENCHMARK.json resolves to its files by name."""
import json
import os
import re

import pytest

from bench.harness import spec

BM = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_keys_and_names():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BM[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(n for n in names)) == len(names) - 0
    assert BM["paths"] == ["bench"] and BM["command"][1] == "bench/run.py"
    e2e = {m["name"] for m in BM["end_to_end"]}
    assert "setup_s" in e2e
    assert all(m["moves"] in e2e for m in BM["per_layer"])


@pytest.mark.parametrize("workload", [w["name"] for w in BM["workloads"]])
def test_workload_resolves(workload):
    cell = spec.cell(workload)
    kind = cell.kind()
    assert {m["name"] for m in cell.end_to_end} == {kind.WORK_METRIC,
                                                   "setup_s"}
    assert cell.end_to_end[-1]["name"] == "setup_s"
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert callable(cell.metric_reader(m["name"]).read)
        assert m["moves"] == kind.WORK_METRIC
    drv = kind.Driver(cell.config, cell.traffic, 1)
    assert set(cell.limits), "every cell has limits"
    for v in cell.limits.values():
        assert 0 <= v["limit"] < float("inf")
    assert hasattr(drv, "check") and hasattr(drv, "failed")


@pytest.mark.parametrize("cfg", BM["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_guarantees(cfg):
    with open(os.path.join(spec.ROOT, cfg["file"])) as f:
        data = json.load(f)
    assert data["name"] == cfg["name"]
    assert data["guarantees"]
    assert set(cfg["reduced"]) == set(data["reduced"])


def test_unknown_workload_and_device_are_errors():
    with pytest.raises(KeyError):
        spec.cell("no_such_cell")
    with pytest.raises(KeyError):
        spec.peaks("TPU v0 imaginary")
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 8.19e11
