"""Telemetry-plane tests (ISSUE 7 satellite d, plus b's health surface):

* metrics core — log-histogram percentile estimates vs exact numpy,
  bucket-edge semantics, clamp-to-observed-range, registry get-or-create
  and kind-conflict errors, Prometheus/JSON export shapes;
* counter thread-safety under genuinely concurrent `route_batch` traffic
  against one shared registry;
* bounded event-bus ring (dropped counter, seq semantics, re-entrant
  subscribers);
* seeded tracer determinism, tracer ring bound, JSONL export and the
  `repro-obs` report renderer;
* health surface end-to-end — a daemon controller's `last_loop_error` sets
  the snapshot to "error" and clears on recovery (with loop_error /
  loop_recovered published on transitions only), `outcomes_dropped`
  surfaces through counter + bus + degraded health;
* ObsServer HTTP endpoints (/metrics, /health 200 vs 503, /events?since=);
* lifecycle events (swap, rebuilds, rollback + cooldown, stage swap,
  demotion) stamped with the right versions while a thread serves;
* the `repro.router.latency` re-export compatibility surface.
"""
import json
import threading
import time
import types
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.control import ControllerConfig, OutcomeStore, RefinementController
from repro.obs import (
    EventBus,
    HealthMonitor,
    LogHistogram,
    MetricsRegistry,
    ObsServer,
    RouteTracer,
    TraceSampler,
    get_registry,
)
from repro.obs.report import render_trace_report
from repro.obs.summary import percentile_stats
from repro.router.gateway import SemanticRouter
from repro.router.tooldb import ToolRecord, ToolsDatabase

D = 16  # embedding dim for the hand-rolled fixture router


def _embed(tokens):
    return np.bincount(
        np.asarray(tokens, np.int64) % D, minlength=D
    ).astype(np.float32)


def _embed_batch(token_lists):
    return np.stack([_embed(t) for t in token_lists])


def _make_router(n_tools=12, **kw):
    rng = np.random.default_rng(0)
    records = [ToolRecord(i, f"t{i}", np.arange(3), 0) for i in range(n_tools)]
    table = rng.standard_normal((n_tools, D)).astype(np.float32)
    db = ToolsDatabase(records, table)
    return SemanticRouter(db, _embed, k=3, **kw), db


def _wait_for(cond, timeout_s=5.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return False


# ------------------------------------------------------------- metrics core


def test_histogram_percentiles_track_numpy():
    rng = np.random.default_rng(7)
    samples = np.exp(rng.normal(loc=0.5, scale=1.2, size=2000))  # ~0.01..50
    h = LogHistogram("lat_ms")
    for v in samples:
        h.record(float(v))
    assert h.count() == len(samples)
    assert h.mean() == pytest.approx(samples.mean())  # exact, not bucketed
    for q in (50.0, 90.0, 99.0):
        exact = float(np.percentile(samples, q))
        est = h.percentile(q)
        # default edges are 10 buckets/decade -> ~26% worst-case relative
        # error; allow 30% slack
        assert abs(est - exact) / exact < 0.30, (q, est, exact)


def test_histogram_empty_and_single_sample_clamp():
    h = LogHistogram("x")
    assert h.percentile(50.0) == 0.0
    s = h.summary()
    assert s["count"] == 0 and s["min"] == 0.0 and s["max"] == 0.0
    h.record(3.7)
    # bucket interpolation is clamped to the observed [min, max]: one sample
    # reports that sample at every percentile, never a bucket edge
    assert h.percentile(50.0) == pytest.approx(3.7)
    assert h.percentile(99.0) == pytest.approx(3.7)
    assert h.summary()["min"] == pytest.approx(3.7)
    assert h.summary()["max"] == pytest.approx(3.7)


def test_histogram_bucket_edge_semantics():
    # searchsorted(side="left"): a value exactly on edge i lands in bucket i
    h = LogHistogram("x", edges=np.array([1.0, 2.0, 4.0]))
    h.record(2.0)  # == edges[1]
    h.record(0.5)  # below lo -> underflow bucket 0
    h.record(5.0)  # above hi -> overflow bucket len(edges)
    counts = h.bucket_counts()
    assert len(counts) == 4  # len(edges) + 1 (overflow)
    np.testing.assert_array_equal(counts, [1, 1, 0, 1])


def test_registry_get_or_create_and_kind_conflicts():
    reg = MetricsRegistry()
    a = reg.histogram("route_phase_ms", phase="embed")
    assert reg.histogram("route_phase_ms", phase="embed") is a
    assert reg.histogram("route_phase_ms", phase="score") is not a
    # label order must not matter for identity
    c1 = reg.counter("c", a="1", b="2")
    assert reg.counter("c", b="2", a="1") is c1
    # one kind per metric name, across label sets
    with pytest.raises(ValueError):
        reg.gauge("route_phase_ms")
    with pytest.raises(ValueError):
        reg.histogram("c")


def test_prometheus_rendering_cumulative_buckets():
    reg = MetricsRegistry()
    reg.counter("hits_total").inc(3)
    reg.gauge("table_version").set(5)
    h = reg.histogram("lat_ms", edges=np.array([1.0, 10.0, 100.0]))
    for v in (0.5, 2.0, 2.0, 50.0, 500.0):
        h.record(v)
    text = reg.render_prometheus()
    lines = text.splitlines()
    assert "# TYPE hits_total counter" in lines
    assert "hits_total 3.0" in lines
    assert "table_version 5.0" in lines
    assert "# TYPE lat_ms histogram" in lines
    # cumulative exposition: each bucket includes everything below it
    assert 'lat_ms_bucket{le="1"} 1' in lines
    assert 'lat_ms_bucket{le="10"} 3' in lines
    assert 'lat_ms_bucket{le="100"} 4' in lines
    assert 'lat_ms_bucket{le="+Inf"} 5' in lines
    assert "lat_ms_sum 554.5" in lines
    assert "lat_ms_count 5" in lines


def test_prometheus_label_values_are_escaped():
    # text-format spec: label values escape backslash, double-quote, and
    # newline (regression: these were emitted raw, producing an exposition
    # a scraper rejects — or worse, silently mis-parses into wrong series)
    reg = MetricsRegistry()
    reg.counter("odd_total", path='a"b\\c\nd').inc()
    text = reg.render_prometheus()
    assert 'odd_total{path="a\\"b\\\\c\\nd"} 1.0' in text.splitlines()
    # backslash is escaped first, so a literal backslash-n label value stays
    # distinct from a real newline after escaping
    reg.counter("odd_total", path="\\n").inc()
    text = reg.render_prometheus()
    assert 'odd_total{path="\\\\n"} 1.0' in text.splitlines()
    assert 'odd_total{path="a\\"b\\\\c\\nd"} 1.0' in text.splitlines()
    assert len(reg.instruments()) == 2


def test_snapshot_shape_and_label_keys():
    reg = MetricsRegistry()
    reg.counter("n_total").inc()
    reg.histogram("ms", phase="embed").record(1.0)
    snap = reg.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["counters"]["n_total"] == 1.0
    summary = snap["histograms"]['ms{phase="embed"}']
    assert summary["count"] == 1
    assert set(summary) == {"count", "mean", "p50", "p90", "p99", "min", "max"}


def test_default_registry_is_process_wide():
    assert get_registry() is get_registry()


# ------------------------------------- counters under concurrent route_batch


def test_counters_exact_under_concurrent_route_batch():
    reg = MetricsRegistry()
    router, _ = _make_router(metrics=reg)
    n_threads, n_calls, batch = 8, 25, 4
    queries = [np.arange(j, j + 4) for j in range(batch)]
    errors = []

    def worker():
        try:
            for _ in range(n_calls):
                router.route_batch(queries)
        except Exception as exc:  # pragma: no cover - failure path
            errors.append(exc)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    total = n_threads * n_calls
    assert reg.counter("route_batches_total").value() == total
    assert reg.counter("route_requests_total").value() == total * batch
    assert reg.histogram("route_batch_ms").count() == total
    for phase in ("embed", "pad", "score", "assemble"):
        assert reg.histogram("route_phase_ms", phase=phase).count() == total
    # no learned stages configured: identity "adapters" and slice-only
    # "reranks" must not be recorded
    assert reg.histogram("route_phase_ms", phase="adapter").count() == 0
    assert reg.histogram("route_phase_ms", phase="rerank").count() == 0
    for step in ("snapshot", "upload", "dispatch", "wait"):
        assert reg.histogram("index_step_ms", step=step).count() == total
    # one copy back per index call: the packed top-K block
    assert reg.counter("index_transfers_total", dir="d2h").value() == total
    assert reg.histogram("route_obs_ms").count() == total


def test_route_tokens_total_counts_the_batch_tokens():
    reg = MetricsRegistry()
    router, _ = _make_router(metrics=reg)
    queries = [np.arange(7), np.arange(3, dtype=np.int32), np.arange(12), [5]]
    router.route_batch(queries)
    assert reg.counter("route_tokens_total").value() == 7 + 3 + 12 + 1
    assert reg.counter("route_requests_total").value() == len(queries)
    router.route_batch(queries[:2])
    assert reg.counter("route_tokens_total").value() == 23 + 10
    assert reg.counter("route_requests_total").value() == len(queries) + 2


# ------------------------------------------------------------------ EventBus


def test_event_bus_ring_bounds_and_seq_semantics():
    bus = EventBus(capacity=4)
    for i in range(10):
        bus.publish("tick", plane="serve", i=i)
    assert len(bus) == 4
    assert bus.dropped == 6
    assert bus.counts() == {"tick": 10}  # lifetime counts survive eviction
    seqs = [e.seq for e in bus.events()]
    assert seqs == [6, 7, 8, 9]
    assert [e.seq for e in bus.events(since_seq=7)] == [8, 9]
    assert bus.events(kind="other") == []
    last = bus.last("tick")
    assert last is not None and last.seq == 9 and last.details["i"] == 9
    assert bus.last("other") is None
    d = last.as_dict()
    assert d["kind"] == "tick" and d["plane"] == "serve" and d["i"] == 9


def test_event_bus_subscriber_may_publish_without_deadlock():
    bus = EventBus()
    bus.subscribe(
        lambda e: bus.publish("echo", plane=e.plane) if e.kind == "ping" else None
    )
    bus.publish("ping", plane="control")
    assert bus.counts() == {"ping": 1, "echo": 1}


# -------------------------------------------------------------------- tracer


def test_trace_sampler_seeded_determinism():
    a = TraceSampler(sample_every=8, seed=42)
    b = TraceSampler(sample_every=8, seed=42)
    seq_a = [a.sample() for _ in range(400)]
    seq_b = [b.sample() for _ in range(400)]
    assert seq_a == seq_b  # same seed + sequence -> identical decisions
    c = TraceSampler(sample_every=8, seed=43)
    assert [c.sample() for _ in range(400)] != seq_a
    # ~1-in-8 Bernoulli: loose bounds, deterministic given the fixed seed
    assert 20 <= sum(seq_a) <= 90
    always = TraceSampler(sample_every=1, seed=0)
    assert all(always.sample() for _ in range(32))


def test_tracer_ring_export_and_report(tmp_path):
    tracer = RouteTracer(sample_every=1, capacity=8, seed=0)
    router, _ = _make_router(metrics=False, tracer=tracer)
    for i in range(12):
        router.route_batch([np.arange(i, i + 3), np.arange(i + 1, i + 4)])
    assert len(tracer) == 8
    assert tracer.dropped == 4
    traces = tracer.traces()
    t = traces[-1]
    assert t.batch_size == 2 and t.bucket == 2  # pow2 bucket of Q=2
    assert t.path == "index:dense"
    names = [name for name, _ in t.spans]
    steps = ["index.snapshot", "index.upload", "index.dispatch", "index.wait"]
    # start order; no learned stages; the index steps lie inside score
    assert names == ["embed", "pad", "score", *steps, "assemble"]
    phases = [n for n in names if not n.startswith("index.")]
    assert t.total_ms >= sum(ms for n, ms in t.spans if n in phases) * 0.5
    assert "embed" in tracer.phase_summaries()

    out = tmp_path / "trace.jsonl"
    assert tracer.export_jsonl(str(out)) == 8
    records = [json.loads(line) for line in out.read_text().splitlines()]
    assert len(records) == 8 and records[0]["spans"].keys() == set(names)
    report = render_trace_report(records)
    assert "8 traces" in report
    assert "index:dense=8" in report
    assert "embed" in report and "total" in report
    assert render_trace_report([]) == "no traces\n"


# ------------------------------------------------------------ health surface


def test_loop_error_sets_health_and_clears_on_recovery():
    bus = EventBus()
    router, db = _make_router(metrics=False)
    store = OutcomeStore(n_tools=len(db), capacity=256)
    controller = RefinementController(
        db,
        store,
        _embed_batch,
        routers=[router],
        config=ControllerConfig(min_events=10**9, max_interval_s=10**9),
        bus=bus,
    )
    monitor = HealthMonitor(routers=[router], controllers=[controller], bus=bus)

    def boom():
        raise RuntimeError("injected step failure")

    controller.step = boom  # shadow the bound method; deleted to recover
    controller.start(interval_s=0.01)
    try:
        assert _wait_for(lambda: bus.last("loop_error") is not None)
        snap = monitor.snapshot()
        assert snap["status"] == "error" and snap["ok"] is False
        assert "injected step failure" in snap["control"][0]["last_loop_error"]

        del controller.step  # next daemon tick runs the real (healthy) step
        assert _wait_for(lambda: bus.last("loop_recovered") is not None)
        assert _wait_for(lambda: controller.last_loop_error is None)
        snap = monitor.snapshot()
        assert snap["status"] == "ok" and snap["ok"] is True
        assert snap["control"][0]["last_loop_error"] is None
    finally:
        controller.stop()
    # transitions only: one error event and one recovery, not one per tick
    assert bus.counts()["loop_error"] == 1
    assert bus.counts()["loop_recovered"] == 1


def test_outcomes_dropped_surfaces_through_counter_bus_and_health():
    reg = MetricsRegistry()
    bus = EventBus()
    router, _ = _make_router(metrics=reg, bus=bus, outcome_capacity=2)
    for i in range(5):
        router.record_outcome(np.arange(3), tool_id=i % 3, outcome=1)
    assert router.outcomes_dropped == 3
    assert reg.counter("route_outcomes_dropped_total").value() == 3
    # the bus sees the first drop only (a transition, not a per-event spam)
    drops = bus.events(kind="outcomes_dropping")
    assert len(drops) == 1 and drops[0].details["dropped"] == 1
    snap = HealthMonitor(routers=[router], bus=bus).snapshot()
    assert snap["status"] == "degraded" and snap["ok"] is True
    assert snap["serving"][0]["outcomes_dropped"] == 3
    assert snap["events"]["counts"]["outcomes_dropping"] == 1


def test_health_snapshot_ok_with_healthy_planes():
    bus = EventBus()
    router, db = _make_router(metrics=False, bus=bus)
    bus.watch_db(db)
    store = OutcomeStore(n_tools=len(db), capacity=256)
    monitor = HealthMonitor(
        routers=[router], indexes=[router.index], stores=[store], bus=bus
    )
    router.route_batch([np.arange(3)])
    snap = monitor.snapshot()
    assert snap["status"] == "ok"
    assert snap["serving"][0]["table_version"] == db.table_version
    assert snap["index"][0]["fresh"] is True
    assert snap["stores"][0] == {
        "n_events": 0, "dropped": 0, "total_ingested": 0,
    }


# ----------------------------------------------------------------- ObsServer


def _get(url):
    with urllib.request.urlopen(url, timeout=5) as resp:
        return resp.status, resp.read().decode()


def test_obs_server_endpoints():
    reg = MetricsRegistry()
    bus = EventBus()
    router, _ = _make_router(metrics=reg, bus=bus)
    router.route_batch([np.arange(3), np.arange(4)])
    bus.publish("tick", plane="serve")
    monitor = HealthMonitor(routers=[router], bus=bus)
    server = ObsServer(monitor, reg, bus).start()
    base = f"http://{server.host}:{server.port}"
    try:
        code, text = _get(base + "/metrics")
        assert code == 200
        assert "# TYPE route_batches_total counter" in text
        assert "route_phase_ms_bucket" in text

        code, text = _get(base + "/health")
        snap = json.loads(text)
        assert code == 200 and snap["status"] == "ok"

        code, text = _get(base + "/events?since=-1")
        assert code == 200
        kinds = [e["kind"] for e in json.loads(text)]
        assert "tick" in kinds

        with pytest.raises(urllib.error.HTTPError) as err:
            _get(base + "/nope")
        assert err.value.code == 404
    finally:
        server.stop()


def test_obs_server_health_returns_503_on_loop_error():
    failing = types.SimpleNamespace(
        last_loop_error=RuntimeError("dead loop"), reports=[]
    )
    server = ObsServer(HealthMonitor(controllers=[failing])).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(f"http://{server.host}:{server.port}/health")
        assert err.value.code == 503
        snap = json.loads(err.value.fp.read())
        assert snap["status"] == "error"
        assert "dead loop" in snap["control"][0]["last_loop_error"]
    finally:
        server.stop()


# -------------------------------------------- lifecycle events under serving


def test_lifecycle_events_reach_the_bus_stamped_while_serving():
    """A table swap, a guard rollback with the controller's cooldown, and a
    stage demotion land while another thread serves `route_batch`: each
    lifecycle event reaches the bus with the versions it acted on, and
    the serving thread raises nothing."""
    from repro.control import GuardConfig, TableGuard
    from repro.learn import StageGuard, StageGuardConfig
    from repro.router.stages import StageSet

    bus = EventBus()
    router, db = _make_router(
        metrics=MetricsRegistry(), tracer=RouteTracer(sample_every=1), bus=bus
    )
    bus.watch_db(db)
    store = OutcomeStore(n_tools=len(db))
    guard = TableGuard(db, GuardConfig(min_samples=32), bus=bus)
    controller = RefinementController(
        db, store, _embed_batch, routers=[router], guard=guard, bus=bus,
        # swaps are driven by hand; the refinement trigger stays cold
        config=ControllerConfig(min_events=10**9, max_interval_s=10**9),
    )
    stage_guard = StageGuard(router, StageGuardConfig(min_samples=32), bus=bus)
    blocks = [[np.arange(j, j + 4) for j in range(lo, lo + 8)]
              for lo in range(0, 32, 8)]
    stop = threading.Event()
    served = [0]
    errors = []

    def serve():
        try:
            while not stop.is_set():
                router.route_batch(blocks[served[0] % len(blocks)])
                served[0] += 1
        except Exception as exc:  # noqa: BLE001 — asserted below
            errors.append(exc)

    def labels(observe, version, good):
        for _ in range(40):  # synthetic labels: deterministic verdicts
            observe(version, [1, 2, 3], [1] if good else [9])

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    try:
        assert _wait_for(lambda: served[0] >= 2, timeout_s=10.0)
        # table: a healthy window on v0, an unannounced bad swap, then a
        # regression the guard rolls back and the controller cools down on
        v0 = db.table_version
        labels(guard.observe, v0, good=True)
        rng = np.random.default_rng(0)
        bad = db.embeddings.copy()
        rng.shuffle(bad, axis=0)
        v_bad = db.swap_table(bad, expect_current=v0)
        controller.step()  # freezes v0's window as the baseline
        labels(guard.observe, v_bad, good=False)
        report = controller.step()
        assert report.guard is not None
        assert report.guard.action == "rolled_back"
        assert "cooldown" in report.reason
        v_restored = db.table_version

        # stages: an out-of-band StageSet swap, then a regression the
        # stage guard demotes
        sv0 = router.stage_version
        labels(stage_guard.observe, sv0, good=True)
        sv_bad = router.set_stages(StageSet())
        assert stage_guard.check().action == "insufficient_data"
        labels(stage_guard.observe, sv_bad, good=False)
        assert stage_guard.check().action == "demoted"
        sv_restored = router.stage_version
        assert router.index.wait_ready(timeout_s=10.0)
        served_at_end = served[0]
        assert _wait_for(lambda: served[0] > served_at_end, timeout_s=10.0)
    finally:
        stop.set()
        t.join(timeout=10.0)
        router.close()
    assert not t.is_alive()
    assert errors == []

    counts = bus.counts()
    for kind in ("swap", "rebuild_start", "rebuild_finish", "rollback",
                 "cooldown", "stage_swap", "demotion"):
        assert counts.get(kind), (kind, counts)
    assert v_bad in [e.details["version"] for e in bus.events(kind="swap")]
    rb = bus.last("rollback").details
    assert (rb["condemned_version"], rb["restored_version"]) == (
        v_bad, v_restored)
    dm = bus.last("demotion").details
    assert (dm["condemned_version"], dm["restored_version"]) == (
        sv_bad, sv_restored)


# ------------------------------------------------------- latency re-exports


def test_router_latency_reexports_obs_summary():
    from repro.obs import summary
    from repro.router import latency

    # satellite (a): one percentile implementation, re-exported for compat
    assert latency.percentile_stats is summary.percentile_stats
    assert latency.LatencyStats is summary.LatencyStats
    stats = latency.percentile_stats([1.0, 2.0, 3.0])
    assert stats.p50_ms == 2.0 and stats.n == 3
    assert set(stats.as_dict()) == {"p50_ms", "p99_ms", "mean_ms", "n"}
    measured = latency.measure_latency(lambda i: i, n_requests=5, warmup=1)
    assert isinstance(measured, latency.LatencyStats) and measured.n == 5
