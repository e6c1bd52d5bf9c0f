"""Benchmark driver: one function per paper table, plus the subsystem
benches (DESIGN.md §8).

  PYTHONPATH=src python -m benchmarks.run [--smoke] [--tables table4,fig4,router]

Two kinds of benchmark live behind one registry and ONE `--smoke` flag:

  * paper tables (`benchmarks.tables.ALL_TABLES` + roofline/kernels) print
    ``name,us_per_call,derived`` CSV rows to stdout;
  * subsystem suites (`router`, `control`, `index`, `learn`) are the recorded-number
    benches — each writes its own ``BENCH_<name>[_smoke].json`` artifact and
    prints its own summary. They are the same entry points CI smoke-runs
    (`scripts/ci_check.sh`), so `--smoke` means the same reduced scale
    everywhere instead of per-file ad-hoc handling.

`--tables all` (default) runs everything, `roofline` only where the dry-run
artifacts it reads exist; a phase that fails fails the run. `--fast` is
kept as a deprecated alias for `--smoke`.
"""
from __future__ import annotations

import argparse
import json
import os
import time


def _suite_registry():
    """name -> run(smoke=..., seed=..., out=...) for the subsystem benches."""
    from benchmarks import (
        cache_bench,
        control_bench,
        flightrec_bench,
        index_bench,
        learn_bench,
        obs_bench,
        router_bench,
        slo_bench,
    )

    return {
        "router": router_bench.run,
        "control": control_bench.run,
        "index": index_bench.run,
        "learn": learn_bench.run,
        "cache": cache_bench.run,
        "obs": obs_bench.run,
        "slo": slo_bench.run,
        "flightrec": flightrec_bench.run,
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced scale everywhere (tables AND suite benches)")
    ap.add_argument("--fast", action="store_true",
                    help="deprecated alias for --smoke")
    ap.add_argument("--tables", default="all",
                    help="comma list of paper tables and/or suites "
                         "(router,control,index,learn,cache,obs,slo,"
                         "flightrec)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    smoke = args.smoke or args.fast

    from benchmarks.context import BenchContext
    from benchmarks.kernel_bench import kernel_rows
    from benchmarks.roofline import DRYRUN_DIR, roofline_rows
    from benchmarks.tables import ALL_TABLES

    suites = _suite_registry()
    # roofline reads the dry-run's JSON artifacts; the default set runs it
    # only where they exist, and an explicit request fails without them
    default_roofline = ["roofline"] if os.path.isdir(DRYRUN_DIR) else []
    want = list(ALL_TABLES) + default_roofline + ["kernels"] + list(suites)
    if args.tables != "all":
        want = args.tables.split(",")
    unknown = [t for t in want
               if t not in ALL_TABLES and t not in suites
               and t not in ("roofline", "kernels")]
    if unknown:
        raise SystemExit(f"unknown benchmark(s): {unknown} "
                         f"(tables: {list(ALL_TABLES)}; suites: {list(suites)})")

    for name in want:
        if name in suites:
            out = f"BENCH_{name}{'_smoke' if smoke else ''}.json"
            print(f"# suite {name} -> {out}", flush=True)
            suites[name](smoke=smoke, seed=args.seed, out=out)

    rows = []
    needs_ctx = any(t in ALL_TABLES for t in want)
    if needs_ctx:
        t0 = time.time()
        ctx = BenchContext.build(seed=args.seed, fast=smoke)
        print(f"# context built in {time.time() - t0:.1f}s", flush=True)
        for tname in want:
            if tname in ALL_TABLES:
                rows.extend(ALL_TABLES[tname](ctx))
    if "roofline" in want:
        rows.extend(roofline_rows())
    if "kernels" in want:
        rows.extend(kernel_rows())

    if rows or needs_ctx:
        print("name,us_per_call,derived")
        for r in rows:
            print(f"{r['name']},{r['us_per_call']},{json.dumps(r['derived'])}")


if __name__ == "__main__":
    main()
