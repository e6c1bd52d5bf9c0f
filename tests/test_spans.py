"""The route path's span recorder (`repro.obs.trace.SpanRecorder`):

* the gateway's phases tile the batch and the index steps nest inside
  `score`; one list feeds `route_phase_ms`, `index_step_ms` and a sampled
  `RouteTrace` with the same durations;
* with no profiler trace active no `TraceAnnotation` is created; with one
  active, every span is one, and a real CPU profiler trace read back by the
  benchmark's reader holds each span, properly nested, at its recorded
  length;
* transfer bytes and copies per call for dense, the exact fallback and
  Pallas-interpret (one copy back: the packed top-K block), and the table
  upload of a build;
* the benchmark's trace reduction with program spans nested inside its
  own: device sums unchanged, idle gaps named after the innermost span;
* the device ops of the score and re-rank programs carry their step in
  the op metadata;
* the offline fit of `build_router`: one sample of each
  `fit_phase_ms{phase=refine|gate|grow}`, one gate decision, the rows the
  fit moved, nothing for the static stage, nothing on the route path.
"""
import glob
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import reranker
from repro.core.retrieval import topk_dense
from repro.index import ToolIndexManager
from repro.kernels.topk_sim.kernel import topk_sim_pallas
from repro.obs import MetricsRegistry, RouteTracer
from repro.obs import trace as trace_mod
from repro.obs.trace import SpanRecorder, current_spans
from repro.router.gateway import INDEX_STEPS, PHASES, SemanticRouter
from repro.router.tooldb import ToolRecord, ToolsDatabase

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce as tr  # noqa: E402

D, T, K = 16, 12, 3
ROUND_TRIP = ("snapshot", "upload", "dispatch", "wait")
PROGRAM_SPANS = tuple(f"route.{p}" for p in PHASES + ("telemetry",)) + tuple(
    f"index.{s}" for s in INDEX_STEPS
)


def _embed(tokens):
    return np.bincount(np.asarray(tokens, np.int64) % D, minlength=D).astype(np.float32)


def _router(backend="dense", backend_opts=None, **kw):
    rng = np.random.default_rng(0)
    records = [ToolRecord(i, f"t{i}", np.arange(3), 0) for i in range(T)]
    table = rng.standard_normal((T, D)).astype(np.float32)
    db = ToolsDatabase(records, table)
    return SemanticRouter(db, _embed, k=K, backend=backend,
                          backend_opts=backend_opts, **kw)


QUERIES = [np.arange(j, j + 4) for j in range(3)]  # Q=3, padded to 4
Q_PAD = 4


def _only_record(hist) -> float:
    assert hist.count() == 1
    return hist.mean()


# ----------------------------------------------------------------- recorder


def test_spans_nest_and_tile():
    spans = SpanRecorder()
    with spans.span("route.a"):
        with spans.span("index.x"):
            pass
        with spans.span("index.y"):
            pass
    with spans.span("route.b"):
        pass
    recs = spans._spans
    assert [s.name for s in recs] == ["route.a", "index.x", "index.y", "route.b"]
    a, x, y, b = recs
    assert a.t0 <= x.t0 <= x.t1 <= y.t0 <= y.t1 <= a.t1 <= b.t0 <= b.t1
    assert spans.under("index.") == [("x", x.ms), ("y", y.ms)]
    assert [n for n, _ in spans.under("route.")] == ["a", "b"]


def test_gateway_phases_tile_the_batch_and_steps_nest_in_score():
    recorders = []
    real = trace_mod.SpanRecorder

    class Keep(real):
        __slots__ = ()

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            recorders.append(self)

    router = _router(metrics=MetricsRegistry())
    import repro.router.gateway as gw

    orig, gw.SpanRecorder = gw.SpanRecorder, Keep
    try:
        router.route_batch(QUERIES)
    finally:
        gw.SpanRecorder = orig
    (rec,) = recorders
    route = [s for s in rec._spans if s.name.startswith("route.")]
    assert [s.name for s in route] == ["route.embed", "route.pad", "route.score",
                                       "route.assemble", "route.telemetry"]
    assert route[0].t0 == rec.t0  # embed starts at the batch's entry stamp
    for prev, nxt in zip(route, route[1:]):
        assert prev.t1 <= nxt.t0  # disjoint, in order
    score = route[2]
    steps = [s for s in rec._spans if s.name.startswith("index.")]
    assert [s.name for s in steps] == [f"index.{s}" for s in ROUND_TRIP]
    for s in steps:
        assert score.t0 <= s.t0 <= s.t1 <= score.t1
    assert sum(s.ms for s in steps) <= score.ms


def test_one_list_feeds_phases_steps_and_the_sampled_trace():
    reg = MetricsRegistry()
    tracer = RouteTracer(sample_every=1, seed=0)
    router = _router(metrics=reg, tracer=tracer)
    router.route_batch(QUERIES)
    (trace,) = tracer.traces()
    got = dict(trace.spans)
    for phase in ("embed", "pad", "score", "assemble"):
        assert got[phase] == _only_record(reg.histogram("route_phase_ms", phase=phase))
    for step in ROUND_TRIP:
        assert got[f"index.{step}"] == _only_record(reg.histogram("index_step_ms", step=step))
    assert "adapter" not in got and "rerank" not in got
    assert trace.total_ms == _only_record(reg.histogram("route_batch_ms"))
    assert reg.histogram("route_obs_ms").count() == 1


def test_trace_ts_is_the_batch_entry():
    tracer = RouteTracer(sample_every=1, seed=0)
    router = _router(metrics=False, tracer=tracer)
    before = trace_mod.clock.wall()
    router.route_batch(QUERIES)
    after = trace_mod.clock.wall()
    (t,) = tracer.traces()
    # stamped at entry: at least the batch's own duration before the end
    assert before - 1e-3 <= t.ts <= after - t.total_ms / 1e3 + 1e-3


class _FakeAnnotation:
    enabled = False
    made: list = []

    def __init__(self, name):
        self.name = name
        _FakeAnnotation.made.append(self)
        self.entered = self.exited = False

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        self.entered = True

    def __exit__(self, *exc):
        self.exited = True


@pytest.mark.parametrize("enabled", [False, True])
def test_annotations_only_while_a_profiler_trace_is_active(monkeypatch, enabled):
    monkeypatch.setattr(trace_mod, "_ANNOTATION", _FakeAnnotation)
    monkeypatch.setattr(_FakeAnnotation, "enabled", enabled)
    monkeypatch.setattr(_FakeAnnotation, "made", [])
    # no metrics, no tracer: the profiler alone turns the spans on
    router = _router(metrics=False)
    router.route_batch(QUERIES)
    made = _FakeAnnotation.made
    if not enabled:
        assert made == []
        return
    assert [a.name for a in made] == [
        "route.embed", "route.pad", "route.score",
        *(f"index.{s}" for s in ROUND_TRIP), "route.assemble",
    ]
    assert all(a.entered and a.exited for a in made)


def test_index_calls_outside_a_batch_record_nothing():
    router = _router(metrics=False)
    assert current_spans().enabled is False
    router.index.topk(np.ones((2, D), np.float32), K)
    assert current_spans()._spans == []
    outer = SpanRecorder()
    with outer.bound():
        with SpanRecorder().bound():
            pass
        assert current_spans() is outer
    assert current_spans() is not outer


def test_profiler_trace_holds_every_span_nested_at_its_recorded_length(tmp_path):
    reg = MetricsRegistry()
    router = _router(metrics=reg)
    router.route_batch(QUERIES)  # compile outside the trace
    reg = MetricsRegistry()
    router = _router(metrics=reg)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("route_batch"):
            router.route_batch(QUERIES)
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    spans = tr.load(path, span_names=("route_batch",) + PROGRAM_SPANS).spans
    by = {}
    for s, e, name in spans:
        assert name not in by, f"{name} twice"
        by[name] = (s, e)
    names = {"route.embed", "route.pad", "route.score", "route.assemble",
             "route.telemetry"} | {f"index.{s}" for s in ROUND_TRIP}
    assert set(by) == names | {"route_batch"}
    outer = by["route_batch"]
    for name in names:
        assert outer[0] <= by[name][0] <= by[name][1] <= outer[1]
    for step in ROUND_TRIP:
        s, e = by[f"index.{step}"]
        assert by["route.score"][0] <= s <= e <= by["route.score"][1]
    for name in names - {"route.telemetry"}:
        kind, label = name.split(".")
        hist = (reg.histogram("route_phase_ms", phase=label) if kind == "route"
                else reg.histogram("index_step_ms", step=label))
        s, e = by[name]
        assert abs((e - s) / 1e6 - _only_record(hist)) < 0.05, name
    s, e = by["route.telemetry"]
    assert abs((e - s) / 1e6 - _only_record(reg.histogram("route_obs_ms"))) < 0.05


# --------------------------------------------------------- transfer bytes


@pytest.mark.parametrize("backend,opts,masked", [
    ("dense", None, False),
    ("pallas", {"use_pallas": True, "interpret": True}, False),
    # the pallas backend takes no masks: a masked batch is the exact fallback's
    ("pallas", {"use_pallas": True, "interpret": True}, True),
])
def test_transfer_bytes_per_call(backend, opts, masked):
    reg = MetricsRegistry()
    router = _router(backend=backend, backend_opts=opts, metrics=reg)
    masks = np.ones((len(QUERIES), T), np.int32) if masked else None
    router.route_batch(QUERIES, masks)
    path = router.index.last_path()
    assert path == ("exact" if masked else f"index:{backend}")
    up = Q_PAD * D * 4 + (Q_PAD * T * 4 if masked else 0)
    down = Q_PAD * K * 8  # float32 scores + int32 indices, in one block
    assert reg.counter("index_transfer_bytes_total", dir="h2d").value() == up
    assert reg.counter("index_transfer_bytes_total", dir="d2h").value() == down
    # the query block (and the mask) up, the packed block down
    assert reg.counter("index_transfers_total", dir="h2d").value() == 1 + masked
    assert reg.counter("index_transfers_total", dir="d2h").value() == 1


def test_build_uploads_count_the_table():
    reg = MetricsRegistry()
    rng = np.random.default_rng(1)
    records = [ToolRecord(i, f"t{i}", np.arange(3), 0) for i in range(T)]
    table = rng.standard_normal((T, D)).astype(np.float32)
    db = ToolsDatabase(records, table)
    h2d = reg.counter("index_transfer_bytes_total", dir="h2d")
    copies = reg.counter("index_transfers_total", dir="h2d")
    ToolIndexManager(db, backend="dense", async_rebuild=False, metrics=reg)
    probe = min(T, 64) * D * 4  # the validation build at construction
    assert h2d.value() == probe + T * D * 4
    assert copies.value() == 2
    db.swap_table(table[::-1].copy(), expect_current=db.table_version)
    assert h2d.value() == probe + 2 * T * D * 4
    assert copies.value() == 3


def test_ivf_is_one_host_span_and_moves_no_bytes():
    reg = MetricsRegistry()
    router = _router(backend="ivf", backend_opts={}, metrics=reg)
    assert router.index.wait_ready(timeout_s=30)
    router.route_batch(QUERIES)
    assert router.index.last_path() == "index:ivf"
    assert reg.histogram("index_step_ms", step="ivf").count() == 1
    assert reg.histogram("index_step_ms", step="wait").count() == 0
    assert reg.counter("index_transfer_bytes_total", dir="h2d").value() == 0
    assert reg.counter("index_transfers_total", dir="d2h").value() == 0


# ---------------------------------------- the benchmark's trace reduction

BENCH_SPANS = [
    (0, 1010, "window"),
    (100, 620, "route_batch"), (110, 200, "embed_batch_fn"), (210, 605, "index.topk"),
    (700, 900, "route_batch"), (710, 800, "embed_batch_fn"), (810, 890, "index.topk"),
]
# the program's own spans, nested as the gateway and the index layer open
# them around and inside the benchmark's wrappers; batch 1 waits long
PROGRAM = [
    (105, 205, "route.embed"), (205, 610, "route.score"),
    (212, 220, "index.snapshot"), (220, 240, "index.upload"), (240, 250, "index.dispatch"),
    (250, 600, "index.wait"), (611, 618, "route.assemble"),
    (705, 800, "route.embed"), (805, 895, "route.score"),
    (812, 820, "index.snapshot"), (820, 840, "index.upload"), (840, 850, "index.dispatch"),
    (850, 885, "index.wait"), (896, 899, "route.assemble"),
]


def _synthetic(spans):
    # the device clock runs 5,000 ns ahead of the host's; run ids place executions
    a = tr.Module(5245, 5300, "jit_topk_dense(7)", host=245, ops=[
        (5245, 5270, "%fusion = f32[64,9]"),
        (5275, 5300, '%custom-call = (f32[64,5]) custom-call(), custom_call_target="TopK"')])
    b = tr.Module(5845, 5900, "jit_topk_dense(7)", host=845,
                  ops=[(5845, 5900, "%fusion = f32[64,9]")])
    return tr.Trace(devices=[[a, b]], spans=list(spans))


def test_program_spans_leave_device_sums_alone_and_name_the_gaps():
    plain = tr.reduce(_synthetic(BENCH_SPANS))
    both = tr.reduce(_synthetic(BENCH_SPANS + PROGRAM))
    assert both.window_s == plain.window_s
    assert both.busy_s == plain.busy_s == pytest.approx(105e-9)
    assert both.executions == plain.executions == 2
    for name in ("index.topk", "route_batch", "embed_batch_fn"):
        assert both.device_s.get(name) == plain.device_s.get(name)
    assert plain.device_s["index.topk"] == pytest.approx(105e-9)
    assert both.device_s["index.dispatch"] == pytest.approx(105e-9)  # both enqueued there
    # the same gaps, named after the innermost span over most of each:
    # before `a` the client waited; before `b` batch 1 sat in its wait for
    # the device and its copy back ([250, 600] of [300, 845]); the tail is
    # the client's again
    assert [g for _, g in both.idle_gaps] == [g for _, g in plain.idle_gaps]
    assert [n for n, _ in plain.idle_gaps] == ["index.topk", "window", "window"]
    assert [n for n, _ in both.idle_gaps] == ["index.wait", "window", "window"]
    segs = tr.flatten(BENCH_SPANS + PROGRAM)
    starts = [s for s, _, _ in segs]
    assert tr.label(580, 600, segs, starts) == "index.wait"  # the one copy back
    assert tr.label(110, 200, segs, starts) == "embed_batch_fn"  # inside route.embed
    assert tr.label(605, 610, segs, starts) == "route.score"


# --------------------------------------------------------- device op names


def _lowered(program):
    q, t = jnp.ones((4, D)), jnp.ones((T, D))
    if program == "topk_dense":
        return topk_dense.lower(q, t, K)
    if program == "topk_sim_pallas":
        return topk_sim_pallas.lower(q, t, K, interpret=True)
    params = reranker.init_mlp(jax.random.PRNGKey(0))
    return reranker.rerank_topk_scored.lower(
        params, jnp.ones((4, 6, 7)), jnp.zeros((4, 6), jnp.int32), 2)


@pytest.mark.parametrize("program,scopes", [
    ("topk_dense", ("topk_dense)/score/", "topk_dense)/topk/")),
    ("topk_sim_pallas", ("topk_sim_pallas)/score/topk/",)),  # one fused op
    ("rerank_topk_scored", ("rerank_topk_scored)/rerank/",)),
])
def test_device_ops_carry_their_step_in_op_metadata(program, scopes):
    hlo = _lowered(program).as_text(debug_info=True)
    for scope in scopes:
        assert scope in hlo


# ------------------------------------------------------------ the offline fit

FIT_PHASES = ("refine", "gate", "grow")


def _fit_counts():
    from repro.obs import get_registry

    reg = get_registry()
    return (
        {p: reg.histogram("fit_phase_ms", phase=p).count() for p in FIT_PHASES},
        {d: reg.counter("refine_gate_total", decision=d).value() for d in ("accepted", "rejected")},
    )


def _route_span_names(bench, stage, **kw):
    """The span names of one sampled `route_batch` of a freshly built router."""
    from repro.launch.serve import build_router

    tracer = RouteTracer(sample_every=1, seed=0)
    router, pipe = build_router(bench, stage, tracer=tracer, **kw)
    try:
        router.route_batch(list(bench.query_tokens[:3]))
    finally:
        router.close()
    (trace,) = tracer.traces()
    return [name for name, _ in trace.spans], pipe


def test_one_fit_records_each_phase_once_and_its_gate(small_bench_sparse):
    small_bench = small_bench_sparse  # many tools with no positive in the fit split
    from repro.obs import get_registry

    phases0, gate0 = _fit_counts()
    names, pipe = _route_span_names(small_bench, "oats-s1", num_tools=2 * small_bench.n_tools)
    phases1, gate1 = _fit_counts()
    assert {p: phases1[p] - phases0[p] for p in FIT_PHASES} == dict.fromkeys(FIT_PHASES, 1)
    accepted = bool(pipe.refine_result.accepted)
    assert {d: gate1[d] - gate0[d] for d in gate1} == {
        "accepted": float(accepted), "rejected": float(not accepted)}
    # the fit split as the pipeline draws it; a tool's positives are its labels there
    train = small_bench.train_idx
    perm = np.random.default_rng(pipe.config.seed).permutation(len(train))
    n_val = max(int(round(pipe.config.gate_val_frac * len(train))), 1)
    fit = train[np.sort(perm[n_val:])]
    with_positive = int((small_bench.relevance_matrix()[fit].sum(0) > 0).sum())
    assert 0 < with_positive < small_bench.n_tools
    assert get_registry().gauge("refine_rows_moved").value() == (with_positive if accepted else 0)
    assert current_spans().enabled is False  # the fit's recorder is unbound again
    # nothing of the fit on the route path: its spans are the static stage's
    assert names == _route_span_names(small_bench, "se")[0]
    assert "score" in names and not [n for n in names if n.startswith("fit")]


def test_the_static_stage_records_no_fit_phase(small_bench):
    before = _fit_counts()
    _, pipe = _route_span_names(small_bench, "se")
    assert pipe.refine_result is None
    assert _fit_counts() == before
