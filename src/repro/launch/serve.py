"""Serving launcher: the OATS gateway in front of a backend pool.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      --requests 32 --max-new-tokens 8

Wires together the full paper pipeline (Fig. 2): a synthetic MetaTool-like
tool database, the OATS offline refinement job (Stage 1 + validation gate +
atomic table swap), the serving path (embed -> top-K -> attach tools), and a
backend model pool doing real prefill+decode: at full width, or on the
`reduced` config under --smoke. A chosen index backend that never becomes
fresh, or a non-finite logit from the pool, is an error, not a fallback.
"""
from __future__ import annotations

import argparse
import signal
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.core.pipeline import OATSPipeline, PipelineConfig, STAGE_PRESETS
from repro.data.benchmarks import make_metatool_like, scale_tool_corpus
from repro.embedding.bag_encoder import BagEncoder
from repro.models import model as M
from repro.models.config import reduced
from repro.obs import (
    EventBus,
    FlightRecorder,
    HealthMonitor,
    JitProfiler,
    ObsServer,
    QualityConfig,
    QualityMonitor,
    RouteTracer,
    SamplingProfiler,
    SLOEngine,
    TimeSeriesRing,
    get_registry,
    stamp_router_costs,
)
from repro.obs.trace import SpanRecorder
from repro.router.gateway import SemanticRouter
from repro.router.latency import measure_latency, percentile_stats
from repro.router.tooldb import ToolRecord, ToolsDatabase


def build_router(
    bench,
    stage: str = "oats-s1",
    k: int = 5,
    backend: str = "dense",
    num_tools: int = 0,
    seed: int = 0,
    tracer=None,
    bus=None,
    quality=None,
    cache=None,
    cleanups=None,
):
    """Gateway over the refined table; `backend` picks the index scorer.

    `num_tools > bench.n_tools` tiles + perturbs the refined table to that
    size (`scale_tool_corpus`) — the MCP-registry-scale demo. Scaled row i
    is a clone of base tool `i % bench.n_tools` (provenance by modulo).

    `cleanups`, when passed, collects the detach handles of any listeners
    this builder registers on the database (bus/quality watches) so the
    caller can unregister them at shutdown instead of leaking them across
    instances.
    """
    detach = (cleanups.append if cleanups is not None else lambda fn: None)
    enc = BagEncoder(bench.vocab)
    # offline control plane: fit the requested OATS stage, then deploy it;
    # one recorder times the fit's phases (fit.refine, fit.gate, fit.grow)
    fit_spans = SpanRecorder()
    with fit_spans.bound():
        pipe = OATSPipeline.fit(bench, PipelineConfig(stages=STAGE_PRESETS[stage], k=k), enc)
    if num_tools and num_tools < bench.n_tools:
        raise SystemExit(
            f"--num-tools {num_tools} is below the native table size "
            f"({bench.n_tools}); the scaler only tiles up — "
            f"use --n-tools for a smaller benchmark"
        )
    if num_tools and num_tools > bench.n_tools:
        base_t = bench.n_tools
        with fit_spans.span("fit.grow"):
            table = scale_tool_corpus(np.asarray(pipe.tool_table), num_tools, seed=seed)
            records = [
                ToolRecord(
                    i,
                    f"tool_{i % base_t}" + ("" if i < base_t else f"_clone{i // base_t}"),
                    bench.desc_tokens[i % base_t],
                    int(bench.tool_category[i % base_t]),
                )
                for i in range(num_tools)
            ]
        db = ToolsDatabase(records, table)  # refined table baked in at scale
        if bus is not None:
            detach(bus.watch_db(db))
        if quality is not None:
            detach(quality.watch_db(db))
    else:
        records = [
            ToolRecord(i, f"tool_{i}", bench.desc_tokens[i], int(bench.tool_category[i]))
            for i in range(bench.n_tools)
        ]
        db = ToolsDatabase(records, enc.encode(bench.desc_tokens))
        # watch BEFORE the deploy swap: every table move — this one, later
        # controller swaps, guard rollbacks, out-of-band deploys — must land
        # on the bus (and refresh the drift detector's reference stats)
        if bus is not None:
            detach(bus.watch_db(db))
        if quality is not None:
            detach(quality.watch_db(db))
        # the §7.2 deploy step, exercised; the db was constructed just above
        # so version 0 is the only possible live version — the CAS still
        # guards against this block ever being reordered after serving starts
        db.swap_table(pipe.tool_table, expect_current=0)
    reg = get_registry()
    for phase, ms in fit_spans.under("fit."):
        reg.histogram("fit_phase_ms", phase=phase).record(ms)
    router = SemanticRouter(
        db,
        embed_fn=lambda toks: enc.encode_one(toks),
        embed_batch_fn=enc.encode,  # one encoder call per route_batch
        k=k,
        backend=backend,
        tracer=tracer,
        bus=bus,
        quality=quality,
        cache=cache,
    )
    # purge version-dead cache entries eagerly on swap/stage_swap (lookup
    # stamps already make stale serves impossible; this reclaims memory and
    # emits the `cache_invalidated` event the runbook watches)
    if cache is not None and bus is not None:
        detach(cache.watch(bus))
    # the chosen backend must be what serves: an index that never becomes
    # fresh would leave the exact dense fallback serving under its name
    if not router.index.wait_ready(timeout_s=300.0):
        router.close()
        raise RuntimeError(
            f"{backend} index never became fresh (stats: {router.index.stats})"
        )
    return router, pipe


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--stage", default="oats-s1", choices=sorted(STAGE_PRESETS))
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--route-batch", type=int, default=16,
                    help="queries per batched route_batch call")
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--n-tools", type=int, default=199)
    ap.add_argument("--n-queries", type=int, default=800)
    ap.add_argument("--backend", default="dense", choices=("dense", "ivf", "pallas"),
                    help="index scorer behind route_batch (repro.index)")
    ap.add_argument("--num-tools", type=int, default=0,
                    help="tile+perturb the tool table to this size "
                         "(> --n-tools; 0 = no scaling) — the index-at-scale demo")
    ap.add_argument("--learn", action="store_true",
                    help="after serving, run one learning-plane step "
                         "(repro.learn) over the logged outcomes: the "
                         "recommend_stages density plan decides whether the "
                         "adapter/re-ranker even train, and any promotion "
                         "is held-out-gated and hot-swapped into the router")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve /metrics (Prometheus), /health (JSON; 503 on "
                         "a failing daemon loop), and /events on "
                         "127.0.0.1:PORT (0 = ephemeral port, printed)")
    ap.add_argument("--trace-every", type=int, default=8,
                    help="route-trace sampling rate (~1-in-N batches)")
    ap.add_argument("--trace-export", metavar="PATH", default=None,
                    help="write sampled route traces as JSONL on exit "
                         "(render with `repro-obs PATH`)")
    ap.add_argument("--dump-dir", metavar="DIR", default=None,
                    help="flight-recorder black-box dumps land here on "
                         "slo_burn/quality_drift/loop_error/rollback/"
                         "demotion or a fatal crash "
                         "(postmortem: `repro-obs replay DIR`)")
    ap.add_argument("--profile-daemons", action="store_true",
                    help="opt-in sampling wall-clock profiler over the "
                         "cadence daemons (exported at /profile)")
    ap.add_argument("--route-cache", action="store_true",
                    help="front route_batch with SemanticRouteCache: "
                         "near-duplicate queries are served the cached "
                         "top-K without paying embed-adjacent score+rerank "
                         "(exact version-stamped invalidation; see "
                         "repro.cache for the config tradeoffs)")
    ap.add_argument("--cache-threshold", type=float, default=0.95,
                    help="min cosine(stored query, new query) to serve a "
                         "cached decision (the correctness knob)")
    ap.add_argument("--cache-capacity", type=int, default=65536,
                    help="retained key slots; one decision occupies "
                         "n_tables (8) slots, LRU-evicted beyond this")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    # telemetry plane: metrics go to the process registry (the router
    # records into it by default), lifecycle events to one shared bus,
    # sampled traces to a bounded ring; the judgement layer (timeseries
    # ring + SLO engine + quality monitor) watches all three
    bus = EventBus()
    tracer = RouteTracer(sample_every=max(args.trace_every, 1), seed=args.seed)
    quality = QualityMonitor(QualityConfig(drift_every=4),
                             registry=get_registry(), bus=bus)
    cleanups = []
    cache = None
    if args.route_cache:
        from repro.cache import CacheConfig, SemanticRouteCache

        cache = SemanticRouteCache(
            CacheConfig(threshold=args.cache_threshold,
                        capacity=args.cache_capacity, seed=args.seed),
            metrics=get_registry(), bus=bus,
        )

    print("== building tool benchmark + OATS control plane ==")
    bench = make_metatool_like(seed=args.seed, n_tools=args.n_tools, n_queries=args.n_queries)
    router, pipe = build_router(
        bench, args.stage, backend=args.backend, num_tools=args.num_tools,
        seed=args.seed, tracer=tracer, bus=bus, quality=quality,
        cache=cache, cleanups=cleanups,
    )
    print(f"== index backend: {args.backend} over {len(router.db)} tools ==")

    ring = TimeSeriesRing(get_registry(), bus=bus)
    slo_engine = SLOEngine(ring, bus=bus, registry=get_registry())
    monitor = HealthMonitor(routers=[router], indexes=[router.index], bus=bus,
                            slo=slo_engine)
    # live compile telemetry over the gateway's hot jits: the router build
    # above warmed them, so the first collect() is the warmup baseline and
    # anything counted after it is a production retrace
    profiler = JitProfiler(registry=get_registry())
    profiler.collect()
    stamp_router_costs(profiler, router, batch_size=args.route_batch)
    recorder = None
    if args.dump_dir:
        recorder = FlightRecorder(
            args.dump_dir, bus=bus, registry=get_registry(), tracer=tracer,
            ring=ring, slo=slo_engine, health=monitor, profiler=profiler,
            routers=[router],
        )
        print(f"== flight recorder armed: dumps -> {args.dump_dir} ==")
    sampler = SamplingProfiler() if args.profile_daemons else None
    obs_server = None
    if args.metrics_port is not None:
        # the ring's cadence is also the SLO judgement cadence (and the
        # compile-cache poll): one daemon snapshots the registry, counts
        # post-warmup jit compiles, and evaluates burn rates on every tick
        ring.start(
            interval_s=1.0,
            on_tick=lambda r: (profiler.collect(), slo_engine.evaluate()),
        )
        if sampler is not None:
            sampler.watch_thread(ring.thread(), "timeseries-ring")
            sampler.start()
        obs_server = ObsServer(monitor, get_registry(), bus,
                               port=args.metrics_port,
                               slo=slo_engine, tracer=tracer,
                               recorder=recorder, profiler=profiler,
                               sampler=sampler).start()
        print(f"== obs: http://{obs_server.host}:{obs_server.port}"
              f"{{/metrics,/health,/events,/slo,/traces,/dumps,/profile}} ==")

    # orderly teardown, shared by the normal exit path and the signal path:
    # recorder first (stop turning shutdown noise into dumps), then the
    # cadence daemons, then the HTTP surface, then the db listeners this
    # process attached — idempotent end to end, so signal-then-finally is
    # safe
    def _shutdown(*_sig):
        if recorder is not None:
            recorder.stop()
        if sampler is not None:
            sampler.stop()
        ring.stop()
        if obs_server is not None:
            obs_server.stop()
        while cleanups:
            cleanups.pop()()

    try:
        # orderly stop on SIGTERM; signal handlers only install from the
        # main thread (tests drive main() from workers — skip there)
        signal.signal(signal.SIGTERM,
                      lambda *sig: (_shutdown(), sys.exit(143)))
    except ValueError:
        pass

    # fatal-exception hook: anything that kills the serving body below
    # becomes one black-box dump before the process dies — the launcher
    # analogue of the controllers' daemon-loop crash hook
    try:
        return _serve_body(args, bench, router, pipe, bus, tracer, quality,
                           monitor)
    except BaseException as exc:
        if recorder is not None and not isinstance(exc, SystemExit):
            recorder.record_crash(exc, source="launch.serve")
        raise
    finally:
        _shutdown()
        router.close()


def _serve_body(args, bench, router, pipe, bus, tracer, quality, monitor):
    print("== loading backend pool ==")
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    params = M.init(cfg, jax.random.PRNGKey(args.seed))
    prefill = jax.jit(lambda p, b: M.prefill(cfg, p, b, max_cache_len=64))
    decode = jax.jit(lambda p, c, b: M.decode_step(cfg, p, c, b))

    test = bench.test_idx[: args.requests]
    hits, lat, n_decoded = 0, [], 0
    t_start = time.time()
    rng = np.random.default_rng(args.seed)
    # 1) router: select tools on CPU (the paper's single-digit-ms path),
    #    batched — each route_batch call scores a whole block of queries in
    #    one jitted top-K pass
    bs = max(args.route_batch, 1)
    results = []
    for lo in range(0, len(test), bs):
        chunk = test[lo : lo + bs]
        results.extend(router.route_batch([bench.query_tokens[q] for q in chunk]))
    base_t = bench.n_tools  # scaled tool i is a clone of base tool i % base_t
    for qi, res in zip(test, results):
        lat.append(res.latency_ms)
        hits += int(any(t % base_t == bench.relevant[qi][0] for t in res.tools))
        # 2) backend: prefill the (stub-tokenized) request + decode new tokens
        prompt_shape = (1, 32, cfg.n_codebooks) if cfg.n_codebooks else (1, 32)
        prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, prompt_shape), jnp.int32)
        batch = {"tokens": prompt}
        if cfg.cross_attn_every:
            batch["image_embeds"] = jnp.zeros((1, cfg.n_image_tokens, cfg.d_model))
        logits, cache = prefill(params, batch)
        finite = jnp.isfinite(logits).all()
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)  # [1,1(,K)]
        for step in range(args.max_new_tokens - 1):
            logits, cache = decode(params, cache, {"token": tok, "pos": jnp.asarray(32 + step, jnp.int32)})
            finite &= jnp.isfinite(logits).all()
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        if not bool(finite):
            raise FloatingPointError(
                f"{cfg.name}: non-finite logits while decoding request {qi}"
            )
        n_decoded += args.max_new_tokens
        # 3) feedback: log the outcome for the next refinement cycle
        for t in res.tools:
            router.record_outcome(bench.query_tokens[qi], t, int(t in bench.relevant[qi]))

    stats = percentile_stats(lat)
    print(
        f"served {len(test)} requests in {time.time() - t_start:.1f}s | "
        f"router R@{router.k}: {hits / len(test):.3f} | "
        f"selection p50={stats.p50_ms:.2f}ms p99={stats.p99_ms:.2f}ms"
    )
    print(f"pool: {cfg.name} decoded {n_decoded} tokens for {len(test)} "
          f"requests, every logit finite")
    print(f"outcome log: {len(router.outcome_log)} events (feeds the next cron refinement)")
    print(f"index stats: {router.index.stats}")
    if router.cache is not None:
        print(f"route cache: hit_rate={router.cache.hit_rate():.3f} "
              f"stats={router.cache.stats}")
    print(f"health: {monitor.snapshot()['status']} | bus events: {bus.counts()}")
    q = quality.summary()
    drift = q["drift_score"]
    print(f"quality: drift_score={drift:.3f} "
          f"(drifting={q['drifting']})" if drift is not None
          else "quality: no drift reference")
    if args.trace_export:
        n = tracer.export_jsonl(args.trace_export)
        print(f"wrote {n} route traces to {args.trace_export} "
              f"(render: repro-obs {args.trace_export})")

    if args.learn:
        from repro.control import OutcomeStore
        from repro.learn import LearnConfig, LearningController

        print("== learning plane: one density-gated step over the outcome log ==")
        store = OutcomeStore(n_tools=len(router.db))
        store.drain_router(router)
        learner = LearningController(
            router.db, store, router, pipe.encoder.encode,
            config=LearnConfig(min_new_events=1, min_queries=10),
            bus=bus,
        )
        report = learner.step()
        plan = report.plan
        print(f"plan: density {plan.density:.2f} ev/tool -> "
              f"{sorted(plan.stages)} ({plan.reason})")
        for stage, d in sorted(report.decisions.items()):
            print(f"  {stage:8s}: {d.action} {d.reason}")
        print(f"live stages: {sorted(report.active) or '(none)'} "
              f"(stage v{report.stage_version})")
    # shutdown (recorder -> daemons -> server -> listeners -> router) runs
    # in main()'s finally via _shutdown, shared with the SIGTERM path
    return stats


if __name__ == "__main__":
    main()
