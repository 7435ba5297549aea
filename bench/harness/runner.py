"""One run of one cell: set up, warm up, measure, check, print one line.

Set-up runs from process start to the first timed answer: imports, the
device, the compile cache, and one warm-up answer of the cell's own traffic
(every program the window calls compiles or loads there).  The window then
starts answers back to back until ``seconds`` have passed, with the traffic's
``ahead`` answers (1 unless it says more) in flight at once; each rate is
all the work completed over the time from the window's start to the end of
its last answer.  With ``trace`` the window runs under the profiler and the
per-layer metrics are read from its trace instead.  Once the window has
closed and peak memory is read, the cell's kind compares its answers with
the plain reference; ``correct`` is whether every number compared is
within its limit and no answer failed.
"""
from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time

from . import compare, device, spec, trace


def _stderr(line: str) -> None:
    print(line, file=sys.stderr, flush=True)


def _traced_window(drv, seconds: float, ahead: int, annotations):
    """Answers under the profiler; returns the records, the work and the
    reduced trace."""
    import jax
    opts = jax.profiler.ProfileOptions()
    # the benchmark's spans are user TraceMe events (host level 1); the
    # Python function tracer would slow the host-bound cells several-fold
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            out = _window(drv, seconds, ahead)
        finally:
            jax.profiler.stop_trace()
        path = trace.find_xplane(tmp)
        events = trace.extract(path, annotations) if path else None
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out, (trace.reduce(events) if events else None)


def _timed_answer(drv, index: int):
    import jax
    ta = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace.ANSWER):
        w, rec = drv.answer(index)
    return w, rec, time.perf_counter() - ta


def _window(drv, seconds: float, ahead: int = 1):
    """Answers back to back for ``seconds``; returns the records (in answer
    order), each answer's time, the work and the window's length.

    ``ahead`` answers are in flight at once, each on a thread of its own, so
    that the host prepares the next answers while the device runs the
    current one and a stall of the host does not leave the chip idle.  When
    the time is up no answer is started; the window closes when every
    started answer has ended, and all of them count."""
    from collections import deque
    from concurrent.futures import ThreadPoolExecutor
    records, times, work = [], [], 0
    started = 0
    pending: deque = deque()
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=ahead) as pool:
        while True:
            while len(pending) < ahead and (
                    started == 0 or time.perf_counter() - t0 < seconds):
                pending.append(pool.submit(_timed_answer, drv, started))
                started += 1
            if not pending:
                break
            w, rec, dt = pending.popleft().result()
            times.append(dt)
            records.append(rec)
            work += w
    return records, times, work, time.perf_counter() - t0


def run(workload: str, seed: int, seconds: float, traced: bool, *,
        started: float, require_chip: bool = True) -> dict:
    """One run; returns the result line as a dict (``compared`` last).
    ``require_chip=False`` skips the look for a TPU, for tests only."""
    cell = spec.cell(workload)
    import jax
    if require_chip:
        devs = device.require_chips(cell.chips)
        spec.peaks(devs[0].device_kind)        # an unknown chip is an error
    else:
        devs = jax.devices()
    cache = device.set_compile_cache()
    clock = device.CompileClock().install()
    kind = cell.kind()
    drv = kind.Driver(cell.config, cell.traffic, seed)
    drv.warm_up()
    setup_s = time.perf_counter() - started
    at_setup = clock.snapshot()

    ahead = int(cell.traffic.get("ahead", 1))
    if traced:
        (records, times, work, window_s), reduced = _traced_window(
            drv, seconds, ahead, kind.ANNOTATIONS)
    else:
        (records, times, work, window_s), reduced = \
            _window(drv, seconds, ahead), None
    in_window = {k: clock.snapshot()[k] - at_setup[k] for k in at_setup}
    peak = device.memory_peak_bytes(devs)
    print(json.dumps({"workload": workload, "seed": seed,
                      "answers": len(times), "window_s": window_s,
                      "answer_s": times, "setup": at_setup,
                      "compiled_in_window": in_window,
                      "memory_peak_bytes": peak, "cache_dir": cache}),
          flush=True)

    failed = sum(bool(drv.failed(r)) for r in records)
    numbers, note = drv.check(records)
    del records
    judged = compare.judge(numbers, cell.limits)
    correct = failed == 0 and all(v["ok"] for v in judged.values())

    d0 = devs[0]
    dev = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(times), "failed": failed}
    if traced:
        metrics = {}
        for m in cell.per_layer:
            value = cell.metric_reader(m["name"]).read(reduced)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        dev["busy_s"] = reduced.busy_s if reduced else 0.0
        dev["window_s"] = reduced.window_s if reduced else 0.0
        result["device"] = dev
        if reduced:
            result["breakdown"] = reduced.breakdown()
    else:
        metrics = {}
        for m in cell.end_to_end:
            value = setup_s if m["name"] == "setup_s" else work / window_s
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = dev
    result["compared"] = {k: {"value": v["value"], "limit": v["limit"]}
                          for k, v in judged.items()}
    _stderr(f"compared ({note}):")
    for k, v in judged.items():
        _stderr(f"  {k} = {v['value']!r}  limit {v['limit']!r}"
                f"{'' if v['ok'] else '  FAILED'}")
    _stderr(f"failed answers = {failed}  limit 0")
    return result
