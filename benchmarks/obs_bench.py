"""Telemetry-plane benchmark: instrumentation overhead + lifecycle smoke.

  PYTHONPATH=src python -m benchmarks.obs_bench [--smoke] [--out BENCH_obs.json]

Two acceptance gates, both enforced with SystemExit (CI smoke-runs this via
scripts/ci_check.sh):

1. **Overhead**: `route_batch` with the full telemetry plane attached
   (MetricsRegistry histograms + counters + gauges, 1-in-64 sampled
   RouteTracer, EventBus, per-batch QualityMonitor drift/score-gap
   collection, a live TimeSeriesRing + SLOEngine judging on a 0.5 s
   cadence, an armed FlightRecorder subscribed to the bus, and a
   JitProfiler polling the hot-path compile caches on the same cadence,
   and a metered never-hit `SemanticRouteCache` so the route cache's
   counters/gauges and `cache` phase span are inside the budget)
   must stay within ``OVERHEAD_BUDGET`` (5 %) of the
   truly bare router (`metrics=False`, no tracer, no bus; an identical
   un-metered never-hit cache keeps the serving work symmetric) on qps. Bare and
   instrumented routers serve identical query blocks slice-interleaved
   inside every round (alternating lead) so CPU frequency drift and
   container noise hit both sides equally; the gate takes the better of
   the peak-of-rounds and median-of-paired-ratios estimates, since their
   noise failure modes are disjoint. Per-phase p50/p99 estimated from the
   live histograms is recorded alongside.

2. **Lifecycle**: a threaded smoke — serving thread routing batches
   concurrently while the main thread drives a table swap, a forced
   TableGuard rollback (+ controller cooldown), index rebuilds, a StageSet
   swap, and a forced StageGuard demotion — must land EVERY expected
   lifecycle event kind on the bus with correct version stamps.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import tempfile
import threading

import numpy as np

OVERHEAD_BUDGET = 0.05  # instrumented route_batch must keep 95% of bare qps
BATCH = 64
TRACE_EVERY = 64  # production-shaped sampling for the overhead measurement
REQUIRED_EVENTS = (
    "swap",  # table deployments (EventBus.watch_db)
    "rebuild_start",  # index lifecycle behind each swap
    "rebuild_finish",
    "rollback",  # TableGuard condemning the bad table
    "cooldown",  # RefinementController purging the condemned-era window
    "stage_swap",  # StageSet deployments (promotion/demotion/out-of-band)
    "demotion",  # StageGuard condemning the bad StageSet
)


def _build_router(bench, enc, metrics, tracer=None, bus=None, quality=None,
                  cache=None):
    from repro.index import ToolIndexManager
    from repro.router.gateway import SemanticRouter
    from repro.router.tooldb import ToolRecord, ToolsDatabase

    db = ToolsDatabase(
        [ToolRecord(i, f"tool_{i}", bench.desc_tokens[i], int(bench.tool_category[i]))
         for i in range(bench.n_tools)],
        enc.encode(bench.desc_tokens),
    )
    if bus is not None:
        bus.watch_db(db)
    if quality is not None:
        quality.watch_db(db)
    index = ToolIndexManager(db, backend="dense", metrics=metrics, bus=bus)
    router = SemanticRouter(
        db, embed_fn=enc.encode_one, embed_batch_fn=enc.encode, k=5,
        index=index, metrics=metrics, tracer=tracer, bus=bus,
        quality=quality, cache=cache,
    )
    return db, router


def _timed_qps(router, blocks, n_calls: int) -> float:
    from repro.obs import clock

    t0 = clock.perf()
    for i in range(n_calls):
        router.route_batch(blocks[i % len(blocks)])
    return n_calls * BATCH / (clock.perf() - t0)


def _timed_pair(bare, inst, blocks, n_calls: int, slices: int = 6):
    """One paired round: bare and instrumented alternate in short slices.

    CPU frequency scaling and container contention drift on ~100 ms
    timescales — longer than a slice, shorter than a round — so measuring
    one full side then the other lets a frequency step charge all its cost
    to whichever side ran second. Slice-interleaving (alternating the
    leading side per slice) makes each round's two accumulated clocks
    sample the same frequency trajectory.
    """
    from repro.obs import clock

    per = max(1, n_calls // slices)
    elapsed = {"bare": 0.0, "inst": 0.0}
    for s in range(slices):
        pair = (("bare", bare), ("inst", inst))
        if s % 2:
            pair = pair[::-1]
        for name, router in pair:
            t0 = clock.perf()
            for i in range(per):
                router.route_batch(blocks[(s * per + i) % len(blocks)])
            elapsed[name] += clock.perf() - t0
    n = per * slices * BATCH
    return n / elapsed["bare"], n / elapsed["inst"]


def run_overhead(bench, enc, smoke: bool, seed: int) -> dict:
    from repro.obs import (
        EventBus,
        FlightRecorder,
        JitProfiler,
        MetricsRegistry,
        QualityConfig,
        QualityMonitor,
        RouteTracer,
        SLOEngine,
        TimeSeriesRing,
        stamp_router_costs,
        stats_from_histogram,
    )

    registry = MetricsRegistry()
    tracer = RouteTracer(sample_every=TRACE_EVERY, seed=seed)
    bus = EventBus()
    # the instrumented side carries the FULL telemetry plane, judgement layer
    # included: per-batch quality/drift collection in route_batch, plus a
    # live TimeSeriesRing cadence evaluating the SLO engine concurrently —
    # the production shape launch/serve.py wires behind --metrics-port.
    # PR 9 adds the memory layer to the same side: an armed FlightRecorder
    # (bus subscriber, idle unless a trigger fires) and a JitProfiler
    # polling the hot-path compile caches on every ring tick.
    quality = QualityMonitor(QualityConfig(drift_every=4),
                             registry=registry, bus=bus)
    # both sides carry a route cache in never-hit mode (threshold=2.0 > any
    # cosine): every batch pays the identical deterministic probe + insert +
    # eviction work, the full embed/score pipeline still runs (no hits to
    # deflate either side), and the bare/instrumented delta stays pure
    # telemetry — now including the cache's counters, gauges, and the
    # per-batch `cache` phase span
    from repro.cache import CacheConfig, SemanticRouteCache

    cache_bare = SemanticRouteCache(CacheConfig(threshold=2.0), metrics=False)
    cache_inst = SemanticRouteCache(CacheConfig(threshold=2.0),
                                    metrics=registry, bus=bus)
    cache_inst.watch(bus)
    _, bare = _build_router(bench, enc, metrics=False, cache=cache_bare)
    _, inst = _build_router(bench, enc, metrics=registry, tracer=tracer,
                            bus=bus, quality=quality, cache=cache_inst)
    ring = TimeSeriesRing(registry, bus=bus)
    engine = SLOEngine(ring, bus=bus, registry=registry)
    profiler = JitProfiler(registry=registry)
    dump_dir = tempfile.mkdtemp(prefix="obs-bench-dumps-")
    recorder = FlightRecorder(dump_dir, bus=bus, registry=registry,
                              tracer=tracer, ring=ring, slo=engine,
                              profiler=profiler, routers=[inst])

    blocks = [
        [bench.query_tokens[qi] for qi in bench.train_idx[lo : lo + BATCH]]
        for lo in range(0, BATCH * 8, BATCH)
    ]
    # smoke keeps enough calls per round that a ring tick or scheduler blip
    # landing mid-round amortizes instead of dominating the round (a 20-call
    # round is ~50 ms; ±1 ms of noise reads as ±2 % "overhead")
    n_calls = 48 if smoke else 60
    rounds = 11 if smoke else 9
    for r in (bare, inst):  # jit warmup + instrument touch, off the clock
        _timed_qps(r, blocks, 3)
    profiler.collect()  # baseline: warmup compiles never count
    stamp_router_costs(profiler, inst, batch_size=BATCH)  # off the clock too

    # judgement cadence runs for the whole measurement: every 0.5 s the ring
    # snapshots the registry, the profiler polls the jit caches, and the
    # engine judges all five default SLOs
    ring.start(interval_s=0.5,
               on_tick=lambda _r: (profiler.collect(), engine.evaluate()))
    ratios, qps_bare_all, qps_inst_all = [], [], []
    for rnd in range(rounds):
        # slice-interleaved inside the round: frequency drift hits both
        # sides equally (see _timed_pair)
        qps_bare, qps_inst = _timed_pair(bare, inst, blocks, n_calls)
        ratios.append(qps_inst / qps_bare)
        qps_bare_all.append(qps_bare)
        qps_inst_all.append(qps_inst)
    ring.stop()
    recorder.stop()
    if ring.last_loop_error is not None:
        raise SystemExit(f"ring daemon flapped during the overhead "
                         f"measurement: {ring.last_loop_error}")
    # a dump here means an SLO burned mid-measurement (noisy host) — recorded
    # for inspection, not gated: flightrec_bench gates dump semantics
    dumps_written = recorder.dumps_written
    shutil.rmtree(dump_dir, ignore_errors=True)
    # two overhead estimators with complementary failure modes: peak-vs-peak
    # assumes noise only subtracts qps (turbo-boost spikes on one side break
    # that), the median of slice-paired per-round ratios assumes slice noise
    # is symmetric (a persistently loaded sibling breaks that). A real
    # instrumentation regression breaches BOTH, so the gate takes the
    # smaller estimate — host noise has to fool two different statistics at
    # once to flake CI, and both readings land in the artifact regardless
    ratio_peak = float(max(qps_inst_all) / max(qps_bare_all))
    ratio_median = float(np.median(ratios))
    ratio = max(ratio_peak, ratio_median)
    overhead = 1.0 - ratio
    phases = {
        name: stats_from_histogram(
            registry.histogram("route_phase_ms", phase=name)
        ).as_dict()
        for name in ("embed", "cache", "pad", "score", "assemble")
    }
    total = stats_from_histogram(registry.histogram("route_batch_ms")).as_dict()
    row = {
        "batch_size": BATCH,
        "n_calls_per_round": n_calls,
        "rounds": rounds,
        "trace_sample_every": TRACE_EVERY,
        "qps_bare_median": float(np.median(qps_bare_all)),
        "qps_instrumented_median": float(np.median(qps_inst_all)),
        "qps_bare_peak": float(max(qps_bare_all)),
        "qps_instrumented_peak": float(max(qps_inst_all)),
        "qps_ratio_median": ratio_median,
        "qps_ratio_peak": ratio_peak,
        "overhead_frac": overhead,
        "overhead_budget": OVERHEAD_BUDGET,
        "n_traces": len(tracer),
        "phase_ms": phases,
        "batch_ms": total,
        "ring_points": len(ring),
        "slo_burning": engine.burning(),
        "drift_batches": quality.summary()["n_batches"],
        "dumps_written": dumps_written,
        "jit_profile": {
            name: {"cache_size": info["cache_size"],
                   "compiles_post_warmup": info["compiles_total"],
                   "flops": (info.get("cost") or {}).get("flops")}
            for name, info in profiler.snapshot()["jits"].items()
        },
    }
    print(f"overhead: peak {100 * (1.0 - ratio_peak):+.2f}% / "
          f"paired-median {100 * (1.0 - ratio_median):+.2f}% -> gate "
          f"{100 * overhead:+.2f}% (budget {100 * OVERHEAD_BUDGET:.0f}%) | "
          f"bare {row['qps_bare_peak']:.0f} qps vs instrumented "
          f"{row['qps_instrumented_peak']:.0f} qps peak | "
          f"{row['n_traces']} traces sampled", flush=True)
    for name, s in {**phases, "total": total}.items():
        print(f"  {name:8s} p50={s['p50_ms']:.3f}ms p99={s['p99_ms']:.3f}ms "
              f"(n={s['n']})", flush=True)
    bare.close()
    inst.close()
    return row


def run_lifecycle(bench, enc, seed: int) -> dict:
    from repro.control import (
        ControllerConfig,
        GuardConfig,
        OutcomeStore,
        RefinementController,
        TableGuard,
    )
    from repro.learn import StageGuard, StageGuardConfig
    from repro.obs import EventBus, RouteTracer
    from repro.router.stages import StageSet

    bus = EventBus()
    tracer = RouteTracer(sample_every=1, seed=seed)
    db, router = _build_router(bench, enc, metrics=False, tracer=tracer, bus=bus)
    store = OutcomeStore(n_tools=len(db))
    guard = TableGuard(db, GuardConfig(min_samples=32), bus=bus)
    controller = RefinementController(
        db, store, enc.encode, routers=[router], guard=guard, bus=bus,
        # the smoke drives swaps by hand; the refinement trigger stays cold
        config=ControllerConfig(min_events=10**9, max_interval_s=10**9),
    )
    stage_guard = StageGuard(router, StageGuardConfig(min_samples=32), bus=bus)

    # concurrent serving: every lifecycle transition below lands while
    # route_batch traffic is in flight on another thread
    stop = threading.Event()
    serve_errors = []
    blocks = [
        [bench.query_tokens[qi] for qi in bench.train_idx[lo : lo + 16]]
        for lo in range(0, 64, 16)
    ]

    def serve_loop():
        i = 0
        try:
            while not stop.is_set():
                router.route_batch(blocks[i % len(blocks)])
                i += 1
        except Exception as exc:  # surfaces as a failed gate below
            serve_errors.append(exc)

    t = threading.Thread(target=serve_loop, name="obs-smoke-serve", daemon=True)
    t.start()

    def observe_table(version, good: bool, n=40):
        for _ in range(n):  # synthetic labels: deterministic guard verdicts
            guard.observe(version, [1, 2, 3], [1] if good else [9])

    def observe_stages(version, good: bool, n=40):
        for _ in range(n):
            stage_guard.observe(version, [1, 2, 3], [1] if good else [9])

    try:
        # act 1: healthy window on v0, then a swap the guard gets a baseline
        # for, then synthetic regression -> rollback + cooldown
        observe_table(db.table_version, good=True)
        rng = np.random.default_rng(seed)
        bad = db.embeddings.copy()
        rng.shuffle(bad, axis=0)
        v_bad = db.swap_table(bad)
        controller.step()  # unannounced swap: baseline frozen from v0
        observe_table(v_bad, good=False)
        report = controller.step()
        rollback_action = report.guard.action if report.guard else None
        v_restored = db.table_version
        cooldown_report = report.reason

        # act 2: StageSet swap, then synthetic regression -> demotion
        sv_before = router.stage_version
        observe_stages(sv_before, good=True)
        sv_bad = router.set_stages(StageSet())
        stage_guard.check()  # unannounced promotion: baseline frozen
        observe_stages(sv_bad, good=False)
        stage_report = stage_guard.check()
        sv_restored = router.stage_version
    finally:
        stop.set()
        t.join(timeout=30)

    counts = bus.counts()
    row = {
        "event_counts": counts,
        "rollback_action": rollback_action,
        "demotion_action": stage_report.action,
        "cooldown_reason": cooldown_report,
        "n_traces": len(tracer),
        "serve_thread_errors": [repr(e) for e in serve_errors],
    }
    print(f"lifecycle: events {counts} | rollback={rollback_action} "
          f"demotion={stage_report.action}", flush=True)

    if serve_errors:
        raise SystemExit(f"serving thread failed during the lifecycle smoke: "
                         f"{serve_errors[0]!r}")
    missing = [k for k in REQUIRED_EVENTS if not counts.get(k)]
    if missing:
        raise SystemExit(f"lifecycle event(s) never reached the bus: {missing} "
                         f"(saw {counts})")
    rb = bus.last("rollback")
    if (rb.details["condemned_version"] != v_bad
            or rb.details["restored_version"] != v_restored):
        raise SystemExit(f"rollback event mis-stamped: {rb.details} "
                         f"(condemned v{v_bad}, restored v{v_restored})")
    dm = bus.last("demotion")
    if (dm.details["condemned_version"] != sv_bad
            or dm.details["restored_version"] != sv_restored):
        raise SystemExit(f"demotion event mis-stamped: {dm.details} "
                         f"(condemned v{sv_bad}, restored v{sv_restored})")
    swap_versions = [e.details["version"] for e in bus.events(kind="swap")]
    if v_bad not in swap_versions:
        raise SystemExit(f"table swap v{v_bad} never reached the bus "
                         f"(saw versions {swap_versions})")
    if "cooldown" not in cooldown_report:
        raise SystemExit(f"rollback step did not enter cooldown: "
                         f"{cooldown_report!r}")
    router.close()
    return row


def run(smoke: bool = False, seed: int = 0, out: str = "BENCH_obs.json") -> dict:
    from repro.data.benchmarks import make_metatool_like
    from repro.embedding.bag_encoder import BagEncoder

    if os.path.dirname(out):
        os.makedirs(os.path.dirname(out), exist_ok=True)

    bench = make_metatool_like(seed=seed, n_tools=199,
                               n_queries=600 if smoke else 1200)
    enc = BagEncoder(bench.vocab)
    overhead = run_overhead(bench, enc, smoke, seed)
    lifecycle = run_lifecycle(bench, enc, seed)
    report = {
        "bench": "telemetry_plane",
        "overhead": overhead,
        "lifecycle": lifecycle,
        "derived": {
            "overhead_frac": overhead["overhead_frac"],
            "overhead_budget": OVERHEAD_BUDGET,
            "lifecycle_events_seen": sorted(
                k for k, v in lifecycle["event_counts"].items() if v
            ),
            "smoke": smoke,
        },
    }
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"telemetry overhead {100 * overhead['overhead_frac']:+.2f}% "
          f"(budget {100 * OVERHEAD_BUDGET:.0f}%) | lifecycle events "
          f"{report['derived']['lifecycle_events_seen']} -> {out}")
    # the overhead gate runs LAST so the artifact is always written for
    # inspection before a violation exits nonzero
    if overhead["overhead_frac"] > OVERHEAD_BUDGET:
        raise SystemExit(
            f"instrumented route_batch overhead "
            f"{100 * overhead['overhead_frac']:.2f}% exceeds the "
            f"{100 * OVERHEAD_BUDGET:.0f}% budget on both estimators "
            f"(peak ratio {overhead['qps_ratio_peak']:.4f}, "
            f"paired-median ratio {overhead['qps_ratio_median']:.4f}; "
            f"peak bare {overhead['qps_bare_peak']:.0f} qps vs instrumented "
            f"{overhead['qps_instrumented_peak']:.0f} qps)"
        )
    return report


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="reduced scale for CI")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_obs.json")
    args = ap.parse_args(argv)
    run(smoke=args.smoke, seed=args.seed, out=args.out)


if __name__ == "__main__":
    main()
