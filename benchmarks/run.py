"""Benchmark harness: one function per paper table/figure (+ subsystem
benches).  Prints ``name,us_per_call,derived`` CSV.  ``--full`` uses
paper-scale sizes (slow); default is CI-sized.  ``--json PATH`` additionally
dumps the rows as JSON for trajectory tracking.

``--json`` merges by row name: when PATH already holds rows from an earlier
(possibly ``--only``-restricted) run, fresh rows replace same-named ones and
new rows append — so partial reruns refine a results file instead of
truncating it to the subset that just ran.  Delete the file for a clean
slate."""
import argparse
import json
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of "
                         "table3|fig3|fig4|fig5|fig6|arch|smr|lease|"
                         "sweep_vec|tropical|obs|net_loopback")
    ap.add_argument("--engine", default="event",
                    choices=("event", "vec", "pallas"),
                    help="fig4/fig6 backend: per-event heap, the "
                         "jax-vectorized sweep engine (repro.vecsim), or "
                         "the same engine relaxing on the Pallas tropical "
                         "kernel")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="dump results as JSON to PATH")
    args = ap.parse_args()

    from . import common

    common.set_compile_cache()
    from . import (arch_microbench, lease_read, net_loopback,
                   obs_overhead, paper_fig3_batching, paper_fig4_scaling,
                   paper_fig5_failures, paper_fig6_robustness,
                   paper_table3_connectivity, smr_throughput, sweep_vec,
                   tropical_bench)

    benches = {
        "table3": paper_table3_connectivity.main,
        "fig3": paper_fig3_batching.main,
        "fig4": lambda full: paper_fig4_scaling.main(full=full,
                                                     engine=args.engine),
        "fig5": paper_fig5_failures.main,
        "fig6": lambda full: paper_fig6_robustness.main(full=full,
                                                        engine=args.engine),
        "arch": arch_microbench.main,
        "smr": smr_throughput.main,
        "lease": lease_read.main,
        "sweep_vec": sweep_vec.main,
        "tropical": tropical_bench.main,
        "obs": obs_overhead.main,
        "net_loopback": net_loopback.main,
    }
    only = set(args.only.split(",")) if args.only else None
    if only and not only <= set(benches):
        ap.error(f"unknown bench(es) {sorted(only - set(benches))}; choose "
                 f"from {'|'.join(benches)}")
    print("name,us_per_call,derived")
    for name, fn in benches.items():
        if only and name not in only:
            continue
        fn(full=args.full)
    if args.json:
        fresh = common.rows()
        merged = merge_rows(_load_existing(args.json), fresh)
        with open(args.json, "w") as fh:
            json.dump(merged, fh, indent=2)
        print(f"wrote {len(merged)} rows to {args.json} "
              f"({len(fresh)} fresh)", file=sys.stderr)


def _load_existing(path: str) -> list:
    try:
        with open(path) as fh:
            existing = json.load(fh)
    except (OSError, ValueError):
        return []
    return existing if isinstance(existing, list) else []


def merge_rows(existing: list, fresh: list) -> list:
    """Merge bench rows by ``name``: fresh rows replace same-named existing
    rows in place (keeping the file's row order stable across partial
    ``--only`` reruns); brand-new rows append at the end."""
    fresh_by_name = {r.get("name"): r for r in fresh}
    merged = [fresh_by_name.pop(r.get("name"), r) for r in existing]
    merged.extend(fresh_by_name.values())
    return merged


if __name__ == "__main__":
    main()
