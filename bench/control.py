#!/usr/bin/env python3
"""Readings that the limits of ``bench/limits/<workload>.json`` are set from.

    python3 bench/control.py --workload <name> --seeds 1,2,3 [--answers k]
                             [--precision float64|float32]

For each seed, in one process, the cell's own path answers ``k`` questions
at the cell's sizes and is compared with the plain reference exactly as a
run compares it; one JSON line per seed gives every number compared.  With
``--precision float64`` (the configurations' precision) these are the lower
readings.  ``--precision float32`` is the control: the same program with
JAX's 64-bit mode switched off, so every float64 array it asks for is made
float32; its numbers are the upper readings, and each run of it must fail
at least one limit.  The benchmark's own runs never run the control.
"""
import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


@contextlib.contextmanager
def lower_precision():
    """Run the program in float32: its 64-bit context becomes a 32-bit one."""
    import jax

    from repro.kernels import compat
    saved = compat.enable_x64
    compat.enable_x64 = lambda: jax.enable_x64(False)
    try:
        yield
    finally:
        compat.enable_x64 = saved


def readings(cell, seeds, answers: int, precision: str):
    """One ``{"seed", "numbers", "failed", "within_limits"}`` per seed."""
    from bench.harness import compare
    kind = cell.kind()
    ctx = lower_precision() if precision == "float32" else \
        contextlib.nullcontext()
    out = []
    with ctx:
        for seed in seeds:
            drv = kind.Driver(cell.config, cell.traffic, seed)
            records = [drv.answer(i)[1] for i in range(answers)]
            failed = sum(bool(drv.failed(r)) for r in records)
            numbers, _note = drv.check(records)
            judged = compare.judge(numbers, cell.limits)
            out.append({"seed": seed, "numbers": numbers, "failed": failed,
                        "within_limits": failed == 0 and all(
                            v["ok"] for v in judged.values())})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--answers", type=int, default=1)
    ap.add_argument("--precision", choices=("float64", "float32"),
                    default="float64")
    args = ap.parse_args(argv)

    from bench.harness import device, spec
    cell = spec.cell(args.workload)
    try:
        device.require_chips(cell.chips)
    except device.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    device.set_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = readings(cell, seeds, args.answers, args.precision)
    for row in rows:
        print(json.dumps({"workload": args.workload,
                          "precision": args.precision, **row}), flush=True)
    worst = {k: max(r["numbers"][k] for r in rows) for k in rows[0]["numbers"]}
    least = {k: min(r["numbers"][k] for r in rows) for k in rows[0]["numbers"]}
    print(json.dumps({"workload": args.workload, "precision": args.precision,
                      "seeds": len(rows), "largest": worst, "smallest": least,
                      "runs_within_limits": sum(r["within_limits"]
                                                for r in rows),
                      "wall_s": time.perf_counter() - STARTED}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
