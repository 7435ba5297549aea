"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each is a JSON file of its
own (``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``).  A
traffic file names its ``kind``, the module ``bench/kinds/<kind>.py`` that
drives the program and checks its answers.  Each per-layer metric is a
reader ``bench/metrics/<metric>.py``, or one that a family of metrics
shares (``device_idle.sweep`` and ``device_idle.clients`` read with
``bench/metrics/device_idle.py``), and each cell's correctness limits are
``bench/limits/<workload>.json``.  Adding a cell adds files; none is edited.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import the file at ``path`` under ``name`` (file names may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]      # the cell's end-to-end metrics, setup_s last
    per_layer: List[dict]       # the cell's per-layer metrics

    def kind(self):
        """The module that drives this cell's traffic (``bench/kinds``)."""
        kind = self.traffic["kind"]
        return load_module(os.path.join(BENCH_DIR, "kinds", f"{kind}.py"),
                           f"bench_kind_{kind}")

    def metric_reader(self, name: str):
        """``bench/metrics/<name>.py``, else the reader its family shares,
        ``bench/metrics/<name up to the first dot>.py``."""
        path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
        if not os.path.exists(path):
            path = os.path.join(BENCH_DIR, "metrics",
                                f"{name.split('.')[0]}.py")
        return load_module(path, "bench_metric_" + name.replace(".", "_"))


def benchmark() -> dict:
    return _load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str) -> Cell:
    """Resolve the workload ``name``; raises ``KeyError`` for an unknown one."""
    bm = benchmark()
    work = {w["name"]: w for w in bm["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(work)}")
    w = work[name]
    configs = {c["name"]: c for c in bm["configs"]}
    cfg = _load_json(os.path.join(ROOT, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(BENCH_DIR, "traffic",
                                      f"{w['traffic']}.json"))
    limits = _load_json(os.path.join(BENCH_DIR, "limits", f"{name}.json"))
    e2e = [m for m in bm["end_to_end"] if _applies(m, name)]
    e2e.sort(key=lambda m: m["name"] == "setup_s")
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name=name, chips=int(w["chips"]), config=cfg,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer)


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of ``device_kind``; an unknown device is an error."""
    table = _load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return table["devices"][device_kind]
