#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and a
traffic mix; see ``bench/harness/spec.py`` for how they are found.  The run
needs a TPU with as many chips as the cell asks for and exits 2 without a
result where JAX finds none.  The last line of standard output is the
result: ``correct``, ``attempted``, ``failed``, ``metrics`` (end-to-end with
``--trace 0``, per-layer with ``--trace 1``), ``device``, with ``--trace 1``
a ``breakdown``, and last the numbers compared beside their limits, which
also end standard error.

A traced run compiles its programs without per-op trace events
(``--xla_enable_hlo_trace=false``, added to ``LIBTPU_INIT_ARGS``, which is
part of JAX's cache key): the round loops of the client cells emit millions
of them, more than the profiler's buffer holds.  Program-level events, all
the per-layer metrics read, stay.
"""
import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.trace:
        os.environ["LIBTPU_INIT_ARGS"] = (
            os.environ.get("LIBTPU_INIT_ARGS", "")
            + " --xla_enable_hlo_trace=false").strip()

    from bench.harness import device, runner
    try:
        result = runner.run(args.workload, args.seed, args.seconds,
                            bool(args.trace), started=STARTED)
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
