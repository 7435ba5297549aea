"""From a profiler trace to device busy time, program times and idle gaps.

Two steps, so that the reduction can be checked on a small recorded fixture
(``bench/tests/fixtures``):

1. :func:`extract` reads an ``.xplane.pb`` with JAX's own reader and keeps
   only what the metrics use: each device's ``XLA Modules`` line (one event
   per program run, named ``jit_<function>(<fingerprint>)``) and the host's
   ``TraceAnnotation`` spans that the benchmark's own files write around
   each call into the program.
2. :func:`reduce` turns those events into :class:`Reduced`: the traced
   window (first to last ``answer`` span), the union of program runs on each
   device within it, the device time of each program, and every idle gap
   with the innermost host span that covered it.

Per-op events are not read: a scan of 78184 rounds writes millions of them.
A program's run is busy time as a whole; the device clock of these events
can differ from the host's by a few milliseconds, so gaps are attributed to
the host span at their midpoint.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

ANSWER = "answer"       # the benchmark's span around each timed answer
MODULES = "XLA Modules"


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def extract(path: str, annotations) -> dict:
    """``{"devices": {plane: [[name, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns], ...]}`` of one trace file; host spans
    are those named in ``annotations``."""
    from jax.profiler import ProfileData
    keep = set(annotations) | {ANSWER}
    pd = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name == MODULES:
                    devices[plane.name] = [
                        [e.name.split("(")[0], float(e.start_ns),
                         float(e.duration_ns)] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events if e.name in keep)
    return {"devices": devices, "host": host}


def _union(intervals):
    """Merge ``(start, end)`` intervals; returns sorted disjoint ones."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclass
class Reduced:
    window_s: float                     # first to last answer span
    busy_s: float                       # device busy, mean over devices
    answers: int                        # answer spans in the window
    modules: Dict[str, float] = field(default_factory=dict)  # s per program
    gaps: List[list] = field(default_factory=list)  # [host span, s], longest first

    def module_seconds(self, match: Callable[[str], bool]) -> float:
        return sum(s for name, s in self.modules.items() if match(name))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.modules.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": self.gaps[:top]}


def reduce(events: dict) -> Optional[Reduced]:
    """The window's busy time, program times and idle gaps; ``None`` when
    the trace holds no answer span or no device."""
    spans = [(n, s, s + d) for n, s, d in events["host"]]
    answers = [(s, e) for n, s, e in spans if n == ANSWER]
    if not answers or not events["devices"]:
        return None
    w0 = min(s for s, _ in answers)
    w1 = max(e for _, e in answers)
    inner = [(n, s, e) for n, s, e in spans if n != ANSWER]
    busy = []
    modules: Dict[str, float] = {}
    gaps = []
    for runs in events["devices"].values():
        clipped = [(max(s, w0), min(s + d, w1), name) for name, s, d in runs
                   if s + d > w0 and s < w1]
        for s, e, name in clipped:
            modules[name] = modules.get(name, 0.0) + (e - s) / 1e9
        merged = _union([(s, e) for s, e, _ in clipped])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                mid = (g0 + g1) / 2
                cover = [(e - s, n) for n, s, e in inner if s <= mid <= e]
                label = min(cover)[1] if cover else "outside the program"
                gaps.append([label, (g1 - g0) / 1e9])
    modules = {k: v / len(events["devices"]) for k, v in modules.items()}
    gaps.sort(key=lambda g: -g[1])
    return Reduced(window_s=(w1 - w0) / 1e9, busy_s=sum(busy) / len(busy),
                   answers=len(answers), modules=modules, gaps=gaps)
