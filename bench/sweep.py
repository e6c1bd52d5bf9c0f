"""Find a configuration's knee: the highest offered rate whose backlog does not grow.

  python3 bench/sweep.py --config <config> --seed <n> \
      --seconds 5 --rates 8000,10000,12000

One process builds the configuration once (as a cell run does), warms every
batch shape, then drives one open-loop window per offered rate, lowest
first, with the steady mix's arrivals and queries. A rate holds when the
window ends with at most one batch cap of requests still waiting to be
handed; above capacity the backlog grows all
through the window, by (offered - capacity) x seconds. Each window prints
one JSON line; the knee is the highest rate below the first that fails to
hold. A TPU is required, as for `run.py`.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True, help="comma-separated routes/s, ascending")
    ap.add_argument("--max-batch", type=int, default=64)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 3
    from bench import cell, loadgen

    bench_dir = ROOT / "bench"
    config = json.loads((bench_dir / "configs" / f"{args.config}.json").read_text())
    cell.use_checkout_cache()
    dep = cell.build(config, args.seed)
    rates = [float(r) for r in args.rates.split(",")]
    pool = dep.pool
    cell.warm(dep.router.route_batch, pool, args.max_batch)
    print(f"sweep {args.config}: set-up {time.perf_counter() - T_START!r} s, "
          f"device {jax.devices()[0].device_kind}", flush=True)
    knee, failed = None, False
    for i, rate in enumerate(rates):
        due = loadgen.arrival_times(rate, args.seconds, args.seed + i)
        queries = [pool[j] for j in loadgen.pool_draws(len(pool), len(due), args.seed + i)]
        with loadgen.gc_paused():
            win = loadgen.drive(dep.router.route_batch, queries, due, args.max_batch,
                                args.seconds)
        s = loadgen.summarize(win)
        holds = win.backlog <= args.max_batch
        failed = failed or not holds
        knee = knee if failed else rate
        print(json.dumps({
            "config": args.config, "offered_per_s": rate, "routes_per_s": s.routes_per_s,
            "backlog": win.backlog, "holds": holds, "mean_batch": s.mean_batch,
            "p50_ms": loadgen.percentile(s.latency_ms, 50),
            "p99_ms": loadgen.percentile(s.latency_ms, 99),
            "service_p50_ms": loadgen.percentile(s.service_ms, 50),
            "service_max_ms": float(s.service_ms.max()) if len(s.service_ms) else None,
            "wait_p99_ms": loadgen.percentile(s.wait_ms, 99),
            "slow_calls": len(loadgen.slow_calls(win)), "errors": len(win.errors)}), flush=True)
    print(json.dumps({"config": args.config, "knee_per_s": knee}), flush=True)
    dep.router.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
