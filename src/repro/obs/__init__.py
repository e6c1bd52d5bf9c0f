"""Telemetry plane: low-overhead metrics, route tracing, events, health.

The paper's pitch is a latency budget ("all mechanisms run within
single-digit millisecond CPU budgets", §5.5); this package makes that
budget *observable at serve time* instead of only in offline benches; the
gateway times its own telemetry per batch (`route_obs_ms`, span
``route.telemetry``, outside `route_batch_ms`). Four surfaces:

* `repro.obs.metrics` — process-wide `MetricsRegistry` of counters, gauges,
  and preallocated log-spaced-bucket histograms (O(1) record, bounded
  memory); Prometheus text exposition + JSON snapshot.
* `repro.obs.trace` — `SpanRecorder`, the route path's one span primitive
  (per-batch ``with spans.span(name):`` blocks on `clock.perf()`, mirrored
  as profiler `TraceAnnotation`s while a profiler trace is active), and the
  seeded ~1-in-N sampled `RouteTracer`: per-batch spans stamped with
  versions, JSONL export, rendered by ``repro-obs`` (`repro.obs.report`).
* `repro.obs.events` — bounded `EventBus` the control/learn/index planes
  publish lifecycle transitions into (replacing scattered prints and
  write-only attributes).
* `repro.obs.health` — `HealthMonitor` JSON snapshot (ok/degraded/error)
  + `ObsServer` HTTP exposition (``/metrics``, ``/health``, ``/events``,
  ``/slo``, ``/traces``), wired into `launch/serve.py` behind
  ``--metrics-port``.

On top of those recorders sits the judgement layer (PR 7):

* `repro.obs.timeseries` — `TimeSeriesRing`, a bounded in-process ring of
  periodic registry snapshots; windowed rates, deltas, and quantiles with
  no external Prometheus (`window_hist`, `rate`, `delta`).
* `repro.obs.slo` — declarative `SLO`s (`default_slos()`: route p99 vs the
  10 ms budget, exact-fallback ratio, guard-rollback rate, drop rate)
  evaluated by `SLOEngine` with multi-window burn rates; transitions
  publish ``slo_burn``/``slo_recovered``, `HealthMonitor` degrades while
  burning, `/slo` serves the snapshot.
* `repro.obs.quality` — `QualityMonitor`: rolling NDCG@5/Recall@5 on
  labelled traffic (via `RollingWindows`, the machinery the guards share),
  top-1/top-2 score-gap confidence, and a label-free query-embedding drift
  detector that publishes ``quality_drift`` *before* the guards have
  enough labels to act.
* exemplars — `LogHistogram.record(value, exemplar=trace_id)` tags the
  bucket with the most recent sampled trace; `percentile_exemplar(99)`
  links a p99 reading to a concrete `RouteTrace` (rendered by
  ``repro-obs watch`` and the `/slo` snapshot).

And on top of the judges sits the memory layer (PR 9) — record → judge →
**remember**:

* `repro.obs.flightrec` — `FlightRecorder`: on a trigger event
  (``slo_burn``, ``quality_drift``, ``loop_error``, ``rollback``,
  ``demotion``) or a fatal crash (`record_crash`, hooked into
  `launch/serve.py` and both controller daemon loops) it freezes the whole
  telemetry state — event ring, sampled traces, metrics snapshot,
  `TimeSeriesRing` window, health/SLO state, version stamps — into one
  atomic, debounced, retention-capped dump directory. ``/dumps`` lists
  them live; ``repro-obs replay <dump-dir>`` renders the postmortem
  timeline offline.
* `repro.obs.profile` — `JitProfiler`: the live twin of PR 5's retrace CI
  leg. Polls the hot-path jits' compile caches
  (`repro.router.gateway.hot_path_jits`) on the ring cadence —
  first collect baselines warmup, after that every cache growth counts as
  ``jit_compiles_total{fn=}`` (feeding `default_slos()`'s
  ``jit_retrace_rate``) — and stamps per-program FLOPs / bytes-accessed
  via XLA ``cost_analysis`` (`stamp_router_costs`), all exported at
  ``/profile``. `SamplingProfiler` adds an opt-in wall-clock sampler over
  the cadence daemons (``--profile-daemons``).

`repro.obs.clock` is the canonical timing module for `router/`, `index/`,
`control/`, and `learn/` (the `obs-discipline` lint rule enforces it), and
`repro.obs.summary` is the one percentile implementation
(`percentile_stats` re-exported from `repro.router.latency` for compat).

Metric catalog (gateway + index layer)
======================================

route_requests_total (counter)
    Queries routed, summed over batches.
route_tokens_total (counter)
    Query tokens embedded, summed over batches (the embed phase's work:
    its cost per batch grows with the tokens it gathers).
route_batches_total (counter)
    `route_batch` calls served.
route_phase_ms{phase=embed|cache|pad|adapter|score|rerank|assemble} (histogram)
    Per-batch wall duration of each serving phase that ran, monotonic
    clock: `pad` is the copy of the miss rows into their power-of-two
    bucket; `adapter` and `rerank` only when that learned stage ran.
route_batch_ms (histogram)
    End-to-end per-batch duration, entry to the end of `assemble` (the
    phases + overhead; the gateway's own telemetry after it is not in it).
route_obs_ms (histogram)
    The gateway's own telemetry per batch: trace record, histogram and
    counter records, score-gap pass (recorded after it, not timing itself).
route_batch_size (histogram)
    Raw batch sizes (pre pow2 padding).
index_step_ms{step=snapshot|upload|dispatch|wait|ivf} (histogram)
    Per-call duration of each index-layer step inside the `score` phase:
    the manager's snapshot + backend lookup, then the device backends' host
    round trip (`repro.index.base.round_trip`) — `upload` the NumPy query
    block (+ mask) in the dtypes that cross (no copy), `dispatch` the
    jitted call, which copies them up, `wait` until the packed top-K block
    is on the host (the device's queue and run land here: dispatch is
    asynchronous; then the block's one copy); or the IVF backend's one
    host span. Recorded by the gateway with its phases.
index_transfer_bytes_total{dir=h2d|d2h} (counter)
    Bytes moved between host and device by the index layer: per call the
    padded query block (+ mask) up and the packed scores + indices down
    (gateway registry), per build the table a device backend uploads
    (manager registry; the same one by default).
index_transfers_total{dir=h2d|d2h} (counter)
    Host-device copies the index layer makes, beside those bytes: per
    device call one up (two with a mask) and one down, the packed block
    (gateway registry); per build one up (manager registry).
fit_phase_ms{phase=refine|gate|grow} (histogram)
    Host duration of each phase of `launch.serve.build_router`'s offline
    fit, one sample per build (one `SpanRecorder` per fit): `refine` the
    OATS-S1 passes and `gate` the validation gate (`core.refine`
    `refine_with_gate`, each until its result is ready; stages with
    refinement only), `grow` the tiled registry's table and tool records
    (`scale_tool_corpus`; only when the registry grows past the fitted
    tools).
refine_gate_total{decision=accepted|rejected} (counter)
    Validation-gate decisions of the offline fit (`OATSPipeline.fit`).
refine_rows_moved (gauge)
    Rows of the table the offline fit deployed that its passes moved: the
    tools with at least one labelled fit query, 0 when the gate rejected.
route_outcomes_dropped_total (counter)
    Outcome-ring overwrites in `record_outcome` (undrained router).
route_cache_hits_total / route_cache_misses_total (counter)
    `SemanticRouteCache` lookup outcomes (a hit = cosine >= threshold on
    a live-stamped entry); hit ratio also exported directly.
route_cache_hit_ratio (gauge)
    Lifetime hits / (hits + misses) — the runbook's headline cache dial.
route_cache_size (gauge)
    Retained key slots (one decision occupies `n_tables` slots).
route_cache_evictions_total (counter)
    LRU slots dropped past `capacity`.
route_cache_invalidated_total (counter)
    Entries purged on version-stamp mismatch (swap/rollback/stage churn).
route_cache_stale_served_total (counter)
    Gateway-tripwire demotions: a cache hit whose stamps no longer match
    the live `(table_version, stage_version)` at serve time. MUST stay 0
    (the ``cache_staleness`` SLO alerts on it; `tests/test_cache.py` holds
    it at 0 under threaded control-plane churn).
index_served_total{path=index|exact} (counter)
    Batches served by the built backend vs the exact dense fallback
    (fallback-serving windows during rebuilds).
index_rebuilds_total / index_build_failures_total (counter)
    Index lifecycle outcomes, mirroring `ToolIndexManager.stats`.
index_build_ms (histogram)
    Build durations (k-means rebuilds dominate).
route_score_gap (histogram)
    Per-query top-1 minus top-2 score (routing confidence; one vectorized
    `record_many` pass, sampled 1-in-4 batches).
quality_ndcg{k=} / quality_recall{k=} (gauge)
    `QualityMonitor`'s rolling labelled-traffic means.
quality_drift_score (gauge)
    RMS z-score of the query-mean EWMA vs the live table's population
    stats (the label-free drift signal).
slo_burning{slo=} / slo_burn_rate{slo=} (gauge)
    Per-SLO breach state (0/1) and worst long-window burn rate, updated
    on every `SLOEngine.evaluate`.
jit_compiles_total{fn=} (counter)
    Post-warmup XLA compiles per hot-path jit (`JitProfiler.collect`
    cache-growth deltas; fn names from `hot_path_jits()`) — the live
    retrace signal behind the ``jit_retrace_rate`` SLO.
jit_cache_size{fn=} (gauge)
    Absolute compile-cache size per hot-path jit (warmup included).
flightrec_dumps_total / flightrec_suppressed_total (counter)
    Black-box dumps written vs suppressed by the debounce window.

Profiler span catalog (`SpanRecorder`, while a profiler trace is active)
======================================================================

route.embed, route.cache, route.pad, route.adapter, route.score,
route.rerank, route.assemble, route.telemetry
    The gateway's phases (histogram label: the name after ``route.``).
index.snapshot, index.upload, index.dispatch, index.wait, index.ivf
    The index layer's steps, inside ``route.score``.
fit.refine, fit.gate, fit.grow
    The offline fit's phases in `build_router` (histogram label: the name
    after ``fit.``), never inside a route batch.

Device ops carry their step in the op metadata: ``score/`` and ``topk/``
(`core.retrieval.topk_dense`, the Pallas top-K kernel), ``rerank/``
(`core.reranker.rerank_topk_scored`).

Event catalog (kind / plane / required detail stamps)
=====================================================

swap / control — version
    Any `ToolsDatabase` version change (via `EventBus.watch_db`): gated
    controller swaps, guard rollbacks, out-of-band deploys.
stage_swap / learn — version
    Any router StageSet change (promotion, demotion, out-of-band).
rollback / control — condemned_version, restored_version, ndcg, baseline
    `TableGuard` condemned the live table and restored a retained one.
demotion / learn — condemned_version, restored_version, ndcg, baseline
    `StageGuard` condemned the live StageSet.
promotion / learn — stage, from_version, to_version, artifact_version
    `LearningController` activated a gated artifact.
gate_reject / control|learn — stage (learn), reason
    A trained candidate failed its held-out gate.
cooldown / control|learn — purged
    Post-rollback/demotion window purge + trigger reset.
rebuild_start, rebuild_finish / index — version, backend (+build_ms)
    Index rebuild lifecycle for one table version.
rebuild_failure / index — version, backend, error
    Build raised; the exact fallback keeps serving.
loop_error / control|learn — controller, error
    A daemon `step()` raised (`last_loop_error` set).
loop_recovered / control|learn — controller
    The next step succeeded (`last_loop_error` cleared).
outcomes_dropping / serve — dropped
    A router's outcome ring overflowed for the first time.
slo_burn / serve — slo, sli, burn (+threshold_ms, p99_ms, p99_exemplar)
    An SLO entered breach: burn > factor over both windows of some pair
    (``sli`` is the SLI kind — latency|ratio|rate).
slo_recovered / serve — slo, sli
    The SLO's next evaluation saw the breach gone.
cache_invalidated / serve — table_version, stage_version, purged, reason
    `SemanticRouteCache` purged >=1 version-stamp-mismatched entries
    (eager path via `cache.watch(bus)`; lazy lookup purges count in
    ``route_cache_invalidated_total`` without an event).
quality_drift / serve — score, threshold, table_version
    The query-population EWMA left the live table's population stats
    (rising edge only; re-arms when the score falls back under).

The flight recorder consumes (never publishes) bus events: its trigger
set is exactly {slo_burn, quality_drift, loop_error, rollback, demotion}
plus out-of-band crashes, and a dump only reads latched judgement state
(`SLOEngine.burning`), so recording can never cause the transitions it
records.
"""
from repro.obs import clock
from repro.obs.events import Event, EventBus
from repro.obs.flightrec import (
    FlightRecorder,
    list_dumps,
    load_dump,
    render_replay,
)
from repro.obs.health import HealthMonitor, ObsServer
from repro.obs.metrics import (
    Counter,
    Gauge,
    LogHistogram,
    MetricsRegistry,
    default_edges,
    get_registry,
)
from repro.obs.profile import JitProfiler, SamplingProfiler, stamp_router_costs
from repro.obs.quality import QualityConfig, QualityMonitor, RollingWindows
from repro.obs.slo import SLO, BurnWindow, SLOEngine, default_slos
from repro.obs.summary import LatencyStats, percentile_stats, stats_from_histogram
from repro.obs.timeseries import HistWindow, TimeSeriesRing
from repro.obs.trace import RouteTrace, RouteTracer, TraceSampler

__all__ = [
    "clock",
    "Event",
    "EventBus",
    "HealthMonitor",
    "ObsServer",
    "Counter",
    "Gauge",
    "LogHistogram",
    "MetricsRegistry",
    "default_edges",
    "get_registry",
    "LatencyStats",
    "percentile_stats",
    "stats_from_histogram",
    "RouteTrace",
    "RouteTracer",
    "TraceSampler",
    "HistWindow",
    "TimeSeriesRing",
    "SLO",
    "BurnWindow",
    "SLOEngine",
    "default_slos",
    "QualityConfig",
    "QualityMonitor",
    "RollingWindows",
    "FlightRecorder",
    "list_dumps",
    "load_dump",
    "render_replay",
    "JitProfiler",
    "SamplingProfiler",
    "stamp_router_costs",
]
