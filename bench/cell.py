"""One run of one cell: build the deployment, warm it, drive the window, check it.

Set-up (counted in `setup_s`, from process start to the first due arrival):
the configuration's tool benchmark is generated from the seed, the router
is built through the program's own `repro.launch.serve.build_router` with
no backend named (so the program's default index serves; its offline fit
runs at the matmul precision the configuration states), the run's
arrivals and queries are drawn, and `route_batch` is called twice at every
power-of-two batch up to the cell's cap, which is every shape the window
uses. The window then drives `route_batch` open loop (`loadgen.drive`).

After the window the device's memory peak is read, the router is closed
and dropped, the table the program deployed is held to the configuration's
table reference (`references/<table_reference>.py`, rebuilt from the
benchmark data and the seed), and every served route is held to its route
reference (`references/<reference>.py`) over that table. None of that is
set-up or window.

With tracing on, the profiler records the window, and the benchmark's own
`TraceAnnotation` spans mark the window, each `route_batch` call, and the
calls into the embed layer (`embed_batch_fn`) and the index layer
(`index.topk`) that it wraps around the router's own.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import glob
import importlib
import shutil
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bench import loadgen, trace_reduce
from bench.manifest import Cell

PHASES = ("embed", "cache", "adapter", "score", "rerank", "assemble")
# JAX's persistent compilation cache: one fixed directory inside the checkout
# (the cache key includes the path), whatever the environment names
CACHE_DIR = Path(__file__).resolve().parents[1] / ".jax_cache"
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


@dataclasses.dataclass
class Deployment:
    router: object
    data: object  # the configuration's tool benchmark (`repro.data.benchmarks.Benchmark`)
    version: int  # table version at the end of set-up
    table: np.ndarray  # that version's table, as the database holds it
    k: int

    @property
    def pool(self) -> list:
        return list(self.data.query_tokens)

    @property
    def word_vecs(self) -> np.ndarray:
        return self.data.vocab.word_vecs


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader (`metrics/<metric>.py`) reads."""

    cell: Cell
    phase: Dict[str, Tuple[int, float]]  # window's delta of route_phase_ms: (count, sum ms)
    reduced: Optional[trace_reduce.Reduced]
    score_calls: List[Tuple[int, int, int, int]]  # (real rows, tools, dim, k) per index call
    peaks: Optional[dict]

    def phase_ms_per_batch(self, phase: str) -> Optional[float]:
        count, total = self.phase.get(phase, (0, 0.0))
        return total / count if count else None


def use_checkout_cache() -> str:
    """Point the compilation cache at `CACHE_DIR`, every program of the route
    path in it however quick to compile. Call before the first compile."""
    import jax

    CACHE_DIR.mkdir(exist_ok=True)  # JAX writes no entry into a missing directory
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(CACHE_DIR)


class CompileCounter:
    """Counts traces and backend compiles anywhere in the process."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event in COMPILE_EVENTS:
            self.count += 1


def build(config: dict, seed: int) -> Deployment:
    """The configuration's deployment, through the program's own builder.

    The offline fit runs under `jax.default_matmul_precision` of the
    configuration's `fit_precision` (none: the program's own default).
    """
    import jax

    from repro.data import benchmarks as data
    from repro.launch.serve import build_router

    bench = getattr(data, config["builder"])(seed=seed)
    precision = config.get("fit_precision")
    with jax.default_matmul_precision(precision) if precision else contextlib.nullcontext():
        router, _ = build_router(bench, config["stage"], k=config["k"],
                                 num_tools=config["num_tools"], seed=seed)
    version, table = router.db.snapshot()
    table = np.asarray(table)
    if table.shape != (config["tools"], config["embedding_dim"]):
        raise RuntimeError(f"built a {table.shape} table, the configuration states "
                           f"({config['tools']}, {config['embedding_dim']})")
    return Deployment(router, bench, version, table, config["k"])


def warm(route: Callable, queries, max_batch: int) -> None:
    """Every shape the window uses: each power-of-two batch up to the cap, twice."""
    b = 1
    while True:
        n = min(b, max_batch)
        for _ in range(2):
            route(list(queries[:n]))
        if n >= max_batch:
            return
        b *= 2


def phase_totals() -> Dict[str, Tuple[int, float]]:
    from repro.obs.metrics import get_registry

    reg = get_registry()
    out = {}
    for p in PHASES:
        h = reg.histogram("route_phase_ms", phase=p)
        n = h.count()
        out[p] = (n, h.mean() * n)
    return out


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def table_check(dep: Deployment, config: dict, seed: int) -> float:
    """The deployed table held to the configuration's table reference."""
    ref = importlib.import_module(f"bench.references.{config['table_reference']}")
    d = dep.data
    tables = ref.build(d.vocab.word_vecs, d.desc_tokens, d.query_tokens, d.relevant,
                       d.candidates, d.train_idx, config["tools"], seed, config["table"],
                       config["k"])
    return ref.table_off_pct(dep.table, tables)


def check(dep: Deployment, queries: list, results: list, config: dict,
          seed: int) -> Tuple[Dict[str, dict], int, dict]:
    """The numbers that decide `correct`, each with its limit; the failed count;
    and everything the references read."""
    ref = importlib.import_module(f"bench.references.{config['reference']}")
    n_tools = dep.table.shape[0]
    routes, failed = [], 0
    for tokens, res in zip(queries, results):
        if res is None:
            failed += 1
            continue
        r = ref.Route(tokens, res.tools, res.scores, res.table_version)
        if not ref.well_formed(r, dep.k, n_tools, dep.version):
            failed += 1
            continue
        routes.append(r)
    got = ref.compare(dep.word_vecs, dep.table, dep.version, routes, dep.k)
    got["table_off_pct"] = table_check(dep, config, seed)
    limits = config["limits"]
    checks = {name: {"value": got[name], "limit": limits[name]} for name in limits}
    checks["failed"] = {"value": failed, "limit": 0}
    checks["compared"] = {"value": got["compared"], "limit": 1}  # at least one, compared below
    return checks, failed, got


def passed(checks: Dict[str, dict]) -> bool:
    return all(
        (c["value"] >= c["limit"]) if name == "compared" else (c["value"] <= c["limit"])
        for name, c in checks.items()
    )


def _traced(router, calls: list):
    """Wrap the router's embed and index calls in the benchmark's spans."""
    from jax.profiler import TraceAnnotation

    inner_topk, inner_embed = router.index.topk, router.embed_batch_fn

    def topk(queries, k, candidate_mask=None):
        with TraceAnnotation("index.topk"):
            out = inner_topk(queries, k, candidate_mask)
        calls.append(k)
        return out

    def embed(queries):
        with TraceAnnotation("embed_batch_fn"):
            return inner_embed(queries)

    def route(queries):
        with TraceAnnotation("route_batch"):
            return router.route_batch(queries)

    router.index.topk, router.embed_batch_fn = topk, embed
    return route


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        peaks: Optional[dict], say: Callable[[str], None] = print,
        build_fn: Callable[[dict, int], Deployment] = build) -> dict:
    """One run of `cell`: its result line, `checks` last. `t_start` is when the
    process started (perf_counter); `say` gets what the run finds, line by line."""
    import jax

    cache_dir = use_checkout_cache()
    compiles = CompileCounter()
    devices = jax.devices()[: cell.chips]
    t = time.perf_counter()
    dep = build_fn(cell.config, seed)
    t_build = time.perf_counter() - t
    w, tr = cell.workload, cell.traffic
    if (tr["arrivals"], tr["queries"]) != ("poisson", "uniform"):
        raise ValueError(f"no traffic {tr['arrivals']!r}/{tr['queries']!r} in bench/loadgen.py")
    due = loadgen.arrival_times(float(w["offered_rate_per_s"]), seconds, seed)
    pool = dep.pool
    queries = [pool[i] for i in loadgen.pool_draws(len(pool), len(due), seed)]
    calls: List[int] = []
    router = dep.router
    route = _traced(router, calls) if trace else router.route_batch
    t = time.perf_counter()
    warm(route, queries, int(w["max_batch"]))
    t_warm = time.perf_counter() - t
    del calls[:]
    phase0, compiles0 = phase_totals(), compiles.count
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans and runtime events only
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tmp, profiler_options=opts)
    span = jax.profiler.TraceAnnotation("window") if trace else contextlib.nullcontext()
    with loadgen.gc_paused(), span:
        setup_s = time.perf_counter() - t_start
        win = loadgen.drive(route, queries, due, int(w["max_batch"]), seconds)
    if trace:
        jax.profiler.stop_trace()
    in_window = compiles.count - compiles0
    phase1 = phase_totals()
    phase = {p: (phase1[p][0] - phase0[p][0], phase1[p][1] - phase0[p][1]) for p in PHASES}
    mem = memory_peak(devices)
    router.close()
    dep.router = router = route = None
    gc.collect()

    say(f"set-up: {setup_s!r} s (tool benchmark and router {t_build!r} s, warm-up "
         f"{t_warm!r} s), compile cache {cache_dir}")
    summ = loadgen.summarize(win)
    say(summ.line(win.offered, win.backlog))
    slow = loadgen.slow_calls(win)
    say(f"calls over 10x the median service: {len(slow)} "
         f"{[(round(t, 3), round(ms, 1)) for t, ms in slow[:20]]} (window s, ms)")
    say(f"compiles inside the window: {in_window}; client woke late p99 "
         f"{loadgen.percentile(win.wake_late * 1e3, 99)!r} ms over {len(win.wake_late)} waits; "
         f"calls that raised: {len(win.errors)}" + (f" (first: {win.errors[0]})" if win.errors else ""))
    t = time.perf_counter()
    checks, failed, got = check(dep, queries[: len(win.due)], win.results, cell.config, seed)
    say(f"reference check of the table and {got['compared']} routes in "
        f"{time.perf_counter() - t!r} s: table_off_pct {got['table_off_pct']!r}, "
        f"score_err {got['score_err']!r}, rank_gap {got['rank_gap']!r}")

    values = {
        "setup_s": setup_s,
        "route_p50_ms": loadgen.percentile(summ.latency_ms, 50),
        "route_p90_ms": loadgen.percentile(summ.latency_ms, 90),
        "routes_per_s": summ.routes_per_s,
    }
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    result = {"correct": passed(checks), "attempted": len(win.due), "failed": failed}
    breakdown = None
    if trace:
        xplanes = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
        reduced = trace_reduce.reduce(trace_reduce.load(xplanes[0])) if xplanes else None
        shutil.rmtree(tmp, ignore_errors=True)
        n_batches = np.bincount(win.batch_of).tolist() if len(win.batch_of) else []
        score_calls = ([(n, dep.table.shape[0], dep.table.shape[1], k)
                        for n, k in zip(n_batches, calls)] if len(calls) == len(n_batches) else [])
        ctx = Context(cell, phase, reduced, score_calls, peaks)
        metrics = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if reduced is not None:
            device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
            breakdown = {"device_ops": [[n, s] for n, s in reduced.top_ops],
                         "idle_gaps": [[n, s] for n, s in reduced.idle_gaps]}
            say(f"trace: {reduced.executions} device executions in the window "
                 f"({reduced.unplaced} not placed), busy {reduced.busy_s!r} s of "
                 f"{reduced.window_s!r} s; device time in spans {reduced.device_s}; "
                 f"idle by host span {reduced.idle_by_span}")
    else:
        metrics = {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
                   for m in cell.end_to_end}
    result.update(metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
