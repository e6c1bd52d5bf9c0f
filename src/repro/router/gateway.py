"""SemanticRouter: the serving-plane gateway (paper Fig. 1b / Fig. 2 top).

Per request: embed the query (CPU), score against the ToolsDatabase
(similarity (+ optional lexical blend) (+ optional MLP re-rank)), attach the
top-K tools, and dispatch to a backend model pool. All learning lives in the
offline control plane (`repro.core`); this module never touches a gradient.

The router is deliberately stateless across requests (production routers are
horizontally-scaled proxies); the mutable state is the swappable embedding
table inside ToolsDatabase, a version-keyed device-side cache of that table
(pure derived state, rebuilt from any snapshot), and the outcome sink.

Outcome handoff: `record_outcome` either pushes each `OutcomeEvent` straight
into an external sink (`outcome_sink=`, typically
`repro.control.OutcomeStore.append` — the control plane's bounded store)
or, with no sink configured, appends to a *bounded, lock-guarded* in-process
buffer that `drain_outcomes()` hands to the refinement job. The buffer is a
ring: an undrained router overwrites its oldest events rather than growing
without limit (`outcomes_dropped` counts the overwrites), and both record
and drain take the same lock, so a drain racing batched serving can never
lose an event. The control plane's `RefinementController` drains attached
routers on every step.

Serving is batch-first: `route_batch` embeds, scores, and top-Ks Q queries
in ONE batched scorer call (plus one batched `rerank_topk_scored` call
when the Stage-2 MLP is enabled), amortizing dispatch overhead across the
whole batch — the hot-path design the paper's single-digit-millisecond
budget assumes at production traffic. `route` is the batch-of-1 special
case and delegates, so batched and sequential serving are equivalent by
construction. `RouteResult.scores` always holds the scores that produced
the final ranking: exact similarities of the reported `table_version` on
every backend's path, f_phi MLP scores when the re-ranker reordered the
candidates.

Scoring itself is pluggable (PR 3): the router delegates to a
`repro.index.ToolIndexManager`, which serves the configured backend
(`dense` exact matmul — the default, numerically the PR 1 path — `ivf`
coarse-quantized candidates + exact re-rank for MCP-registry-scale tables,
or `pallas` fused kernel on TPU) and falls back to exact dense scoring on
the live snapshot whenever the index is stale (mid-rebuild after a
control-plane `swap_table`/`rollback`) or the batch carries candidate masks
the backend cannot honor. The swap/rollback protocol is untouched: scores
and `table_version` always come from the same atomic snapshot.

Learned stages are hot-swappable (PR 4): the adapter head and the Stage-2
re-ranker live in one immutable `StageSet` behind a version counter with
the exact discipline the table has. `route_batch` reads ONE stage snapshot
at entry (the adapter is applied to the query block before the index
backend scores — query-side only, so promotions never invalidate a built
index — and the re-ranker params come from the same snapshot), so an
in-flight batch finishes on the stages it started with even while the
learning plane promotes or demotes mid-batch. `set_stages` is
compare-and-swap (ConflictError on a lost race), superseded sets are
retained in a bounded history, and `rollback_stages` restores one — the
learning plane's `StageGuard` demotion hinge. `RouteResult.stage_version`
reports the snapshot that produced the scores, next to `table_version`.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict, deque
from typing import Callable, Deque, List, Optional, Sequence, Tuple, Union

import jax.numpy as jnp
import numpy as np

from repro.common.bucketing import pad_amount
from repro.core import reranker as reranker_lib
from repro.core.features import OutcomeFeaturizer
from repro.core.retrieval import NEG_INF
from repro.index import ToolIndexManager
from repro.obs import clock
from repro.obs.metrics import MetricsRegistry, get_registry
from repro.obs.trace import SpanRecorder
from repro.router.stages import StageSet
from repro.router.tooldb import ConflictError, ToolsDatabase

__all__ = [
    "RouteResult",
    "OutcomeEvent",
    "SemanticRouter",
    "StageSet",
    "hot_path_jits",
]

PHASES = ("embed", "cache", "pad", "adapter", "score", "rerank", "assemble")
# index-layer steps inside the score phase (`repro.index.base.round_trip`,
# the manager's snapshot, the IVF backend's one host span)
INDEX_STEPS = ("snapshot", "upload", "dispatch", "wait", "ivf")


def hot_path_jits() -> "OrderedDict[str, Callable]":
    """The jitted entry points `route_batch` dispatches to, by name.

    This is the single registry of "programs whose compile behavior is a
    serving concern": `analysis.retrace.hot_path_monitor` (the CI leg) and
    `obs.profile.JitProfiler` (the live compile/cost telemetry) both source
    from it, so adding a jit to the hot path automatically puts it under
    both the offline invariant and the production counters.
    """
    from repro.core import retrieval
    from repro.router import stages as stages_mod

    return OrderedDict(
        (
            ("topk_dense", retrieval.topk_dense),
            ("adapter_apply", stages_mod._adapter_apply_j),
            ("rerank_topk_scored", reranker_lib.rerank_topk_scored),
        )
    )


class _GatewayInstruments:
    """The gateway's metric handles, resolved once at construction.

    Instrument lookup is a dict hit in MetricsRegistry but still costs a
    lock; the hot path must touch preresolved objects only. Catalog:
    `repro.obs` package docstring."""

    def __init__(self, registry: MetricsRegistry):
        self.registry = registry
        self.requests = registry.counter("route_requests_total")
        self.tokens = registry.counter("route_tokens_total")
        self.batches = registry.counter("route_batches_total")
        self.batch_ms = registry.histogram("route_batch_ms")
        self.batch_size = registry.histogram("route_batch_size")
        self.phase = {
            name: registry.histogram("route_phase_ms", phase=name)
            for name in PHASES
        }
        self.index_step = {
            name: registry.histogram("index_step_ms", step=name)
            for name in INDEX_STEPS
        }
        self.transfer_bytes = {
            d: registry.counter("index_transfer_bytes_total", dir=d)
            for d in ("h2d", "d2h")
        }
        self.transfers = {
            d: registry.counter("index_transfers_total", dir=d)
            for d in ("h2d", "d2h")
        }
        self.obs_ms = registry.histogram("route_obs_ms")
        self.outcomes_dropped = registry.counter("route_outcomes_dropped_total")
        # top-1/top-2 score gap per query (routing confidence; a collapsing
        # gap means the router is guessing) — recorded via record_many, one
        # vectorized pass per batch, so per-query cost stays O(1/batch)
        self.score_gap = registry.histogram("route_score_gap")
        # tripwire: cache entries whose version stamps failed the gateway's
        # independent re-check against the live pair. Such entries are
        # demoted to misses (never served), so any non-zero value means a
        # cache bug was caught — the cache_staleness SLO holds this at 0.
        self.cache_stale = registry.counter("route_cache_stale_served_total")


@dataclasses.dataclass
class RouteResult:
    tools: List[int]  # selected tool ids (top-K)
    scores: List[float]  # the scores the final ranking was computed from
    latency_ms: float  # per-query share of the (possibly batched) route call
    pool: str  # backend pool the request was dispatched to
    table_version: int
    # version of the StageSet snapshot that scored this batch: together with
    # table_version it fully determines the scores (the learning plane's
    # StageGuard keys its shadow windows on it)
    stage_version: int = 0
    # True when this result was served from the SemanticRouteCache (its
    # tools/scores were computed by an earlier batch under the SAME
    # (table_version, stage_version) pair reported above)
    cache_hit: bool = False


@dataclasses.dataclass
class OutcomeEvent:
    """A logged outcome tuple (q_j, t_i, o_j) (§4.1 step 1)."""

    query_tokens: np.ndarray
    tool_id: int
    outcome: int  # {0, 1}
    timestamp: float


class SemanticRouter:
    def __init__(
        self,
        db: ToolsDatabase,
        embed_fn: Callable[[np.ndarray], np.ndarray],  # tokens -> [384]
        k: int = 5,
        mlp_params: Optional[dict] = None,
        featurizer: Optional[OutcomeFeaturizer] = None,
        candidate_multiplier: int = 5,
        pool_selector: Optional[Callable[[np.ndarray, List[int]], str]] = None,
        embed_batch_fn: Optional[Callable[[Sequence[np.ndarray]], np.ndarray]] = None,
        outcome_capacity: int = 65_536,
        outcome_sink: Optional[Callable[["OutcomeEvent"], None]] = None,
        index: Optional[ToolIndexManager] = None,
        backend: str = "dense",
        backend_opts: Optional[dict] = None,
        stages: Optional[StageSet] = None,
        stage_history_limit: int = 4,
        metrics: Union[MetricsRegistry, bool, None] = None,
        tracer: Optional["RouteTracer"] = None,  # repro.obs.trace
        bus: Optional["EventBus"] = None,  # repro.obs.events
        quality: Optional["QualityMonitor"] = None,  # repro.obs.quality
        cache: Optional["SemanticRouteCache"] = None,  # repro.cache
    ):
        self.db = db
        self.embed_fn = embed_fn
        self.k = k
        # learned stages live in one immutable snapshot behind a version
        # counter (the table discipline applied to the adapter/re-ranker):
        # constructor args mlp_params/featurizer seed the initial set for
        # backwards compatibility with pre-learning-plane callers
        assert stage_history_limit >= 1
        if stages is None:
            stages = StageSet(mlp_params=mlp_params, featurizer=featurizer)
        else:
            assert mlp_params is None and featurizer is None, (
                "pass learned stages either via stages= or via "
                "mlp_params=/featurizer=, not both"
            )
        self._stages = stages
        self._stage_version = 0
        self._stage_history: "OrderedDict[int, StageSet]" = OrderedDict()
        self._stage_history_limit = int(stage_history_limit)
        self._stage_lock = threading.Lock()
        self.candidate_multiplier = candidate_multiplier
        self.pool_selector = pool_selector or (lambda q, tools: "default")
        # batched encoder (one call for Q queries); falls back to looping
        # embed_fn so any single-query encoder still works batch-first
        self.embed_batch_fn = embed_batch_fn
        # bounded ring: record under lock, drain under the same lock — the
        # discipline ToolsDatabase uses for its table (a lock-free list drops
        # events when a drain races batched serving). `outcome_sink` bypasses
        # the ring entirely: events go straight to the control-plane store.
        self.outcome_log: Deque[OutcomeEvent] = deque()
        assert outcome_capacity >= 1, "outcome_capacity must be >= 1"
        self.outcome_capacity = int(outcome_capacity)
        self.outcomes_dropped = 0
        self.outcome_sink = outcome_sink
        self._outcome_lock = threading.Lock()
        # the scoring layer: a shared ToolIndexManager, or one owned by this
        # router built from (backend, backend_opts) — "dense" is the PR 1
        # jitted topk_dense path, numerics unchanged
        self._owns_index = index is None
        # an owned manager inherits this router's bus at construction so its
        # very first build publishes rebuild events (attaching a bus after
        # the fact races the constructor's async build thread); a shared
        # manager keeps whatever bus its creator wired
        self.index = index if index is not None else ToolIndexManager(
            db, backend=backend, backend_opts=backend_opts, bus=bus
        )
        # telemetry: metrics default ON against the process registry
        # (`benchmarks/obs_bench.py` bounds the cost in CI at <5 % of bare
        # qps); `metrics=False` is the truly bare hot path the bench
        # compares against. Instruments are resolved once here so
        # `route_batch` never takes the registry lock.
        if metrics is False:
            self._obs: Optional[_GatewayInstruments] = None
        else:
            registry = metrics if isinstance(metrics, MetricsRegistry) else get_registry()
            self._obs = _GatewayInstruments(registry)
        self._tracer = tracer
        self._gap_tick = 0  # score-gap 1-in-4 batch sampling counter
        self._bus = bus
        # streaming quality observability (repro.obs.quality): route_batch
        # feeds it raw query embeddings for label-free drift detection
        self._quality = quality
        # near-duplicate route cache (repro.cache): probed after embed
        # (keys are embedding-space), so a hit skips the index backend and
        # the Stage-2 re-ranker for its row. Wire `cache.watch(bus)` at the
        # launcher for eager invalidation on swap/stage_swap events.
        self._cache = cache

    @property
    def cache(self):
        """The attached SemanticRouteCache, if any (read-only view for
        health surfaces and launch summaries)."""
        return self._cache

    def close(self) -> None:
        """Tear down a retiring router (idempotent).

        Unregisters the router-owned index manager from the database's swap
        listeners — without this, a discarded router over a long-lived
        ToolsDatabase keeps rebuilding its index (and pinning its table
        copies) on every future swap. A shared manager passed via `index=`
        is left alone: its lifecycle belongs to the caller.
        """
        if self._owns_index:
            self.index.close()

    # --------------------------------------------------------- learned stages
    @property
    def mlp_params(self) -> Optional[dict]:
        """Live re-ranker params (read-only view of the current StageSet)."""
        return self._stages.mlp_params

    @property
    def featurizer(self) -> Optional[OutcomeFeaturizer]:
        return self._stages.featurizer

    @property
    def stage_version(self) -> int:
        return self._stage_version

    def stage_set(self) -> Tuple[int, StageSet]:
        """(version, StageSet) read atomically w.r.t. promotions — the
        stage-side analogue of `ToolsDatabase.snapshot()`."""
        with self._stage_lock:
            return self._stage_version, self._stages

    def set_stages(
        self, stages: StageSet, expect_version: Optional[int] = None
    ) -> int:
        """Atomically deploy a new StageSet (returns the new version).

        The outgoing set is retained as a demotion target (bounded history,
        oldest evicted first). `expect_version` makes activation
        compare-and-swap: a promotion gated against stage version N is
        refused (ConflictError) if another deployment landed past N while it
        was being trained — mirroring `swap_table(expect_current=...)`.
        """
        with self._stage_lock:
            if expect_version is not None and self._stage_version != expect_version:
                raise ConflictError(
                    f"stages are v{self._stage_version}, not v{expect_version} "
                    f"the promotion was gated against; refusing activation"
                )
            self._stage_history[self._stage_version] = self._stages
            while len(self._stage_history) > self._stage_history_limit:
                self._stage_history.popitem(last=False)
            self._stages = stages
            self._stage_version += 1
            version = self._stage_version
        # publish outside the stage lock: subscribers must never be able to
        # stall a promotion racing the serving path's stage_set() read
        if self._bus is not None:
            self._bus.publish("stage_swap", plane="learn", version=version)
        return version

    def retained_stage_versions(self) -> List[int]:
        """Stage versions available as demotion targets, oldest first."""
        with self._stage_lock:
            return list(self._stage_history.keys())

    def rollback_stages(
        self,
        to_version: Optional[int] = None,
        expect_current: Optional[int] = None,
    ) -> int:
        """Instant demotion to a retained StageSet (returns the new version).

        Same semantics as `ToolsDatabase.rollback`: the restore is itself a
        version bump, the condemned set is not retained, retained sets newer
        than the target are dropped, and `expect_current` refuses
        (ConflictError) when another promotion landed after the caller
        judged `expect_current` — the StageGuard's safety hinge.
        """
        with self._stage_lock:
            if expect_current is not None and self._stage_version != expect_current:
                raise ConflictError(
                    f"stages are v{self._stage_version}, not the judged "
                    f"v{expect_current}; refusing demotion"
                )
            if not self._stage_history:
                raise RuntimeError("no previous stage set to roll back to")
            if to_version is None:
                to_version = next(reversed(self._stage_history))
            if to_version not in self._stage_history:
                raise RuntimeError(
                    f"stage version {to_version} not retained "
                    f"(available: {list(self._stage_history.keys())})"
                )
            stages = self._stage_history.pop(to_version)
            for v in [v for v in self._stage_history if v > to_version]:
                del self._stage_history[v]
            self._stages = stages
            self._stage_version += 1
            version = self._stage_version
        if self._bus is not None:
            self._bus.publish(
                "stage_swap", plane="learn", version=version,
                restored_version=to_version,
            )
        return version

    # ---------------------------------------------------------- serving path
    def _embed_batch(self, queries: Sequence[np.ndarray]) -> np.ndarray:
        if self.embed_batch_fn is not None:
            return np.asarray(self.embed_batch_fn(queries), dtype=np.float32)
        return np.stack([np.asarray(self.embed_fn(q), np.float32) for q in queries])

    def route_batch(
        self,
        queries: Sequence[np.ndarray],
        candidate_masks: Optional[np.ndarray] = None,  # [Q, T] {0,1} or None
    ) -> List[RouteResult]:
        """Route Q queries in one batched scoring pass.

        One batched index call (the configured `ScorerBackend`; exact jitted
        dense by default) scores the whole [Q, D] query block against the
        [T, D] table (with optional per-query candidate masks); when the
        Stage-2 MLP is configured, featurization and `rerank_topk_scored`
        also run over the full batch. Returns one RouteResult per query, in
        input order; each carries the per-query amortized latency. A
        candidate mask admitting fewer than k tools yields a correspondingly
        shorter tools/scores list (never masked-out ids).
        """
        t0 = clock.perf()
        n_q = len(queries)
        if n_q == 0:
            return []
        # ONE stage snapshot per batch: a promotion/demotion landing mid-call
        # cannot mix stage configurations within the batch, and the reported
        # stage_version is the set that actually produced the scores
        stage_version, stages = self.stage_set()
        obs = self._obs
        tracing = self._tracer is not None and self._tracer.sample()
        timed = tracing or obs is not None
        # spans exist only for work that actually ran: the cache span only
        # when a cache is attached, pad/score only when misses reached the
        # index, adapter/rerank only when that learned stage ran — recording
        # ~0 ms identity "adapters" or slice-only "reranks" (or all-hit
        # "scores") would poison the p50
        spans = SpanRecorder(enabled=timed, t0=t0)
        with spans.span("route.embed", start=t0):
            q = self._embed_batch(queries)  # [Q, D]
        # cache probe (repro.cache): keys are embedding-space, so it runs
        # after embed and before everything a hit row gets to skip (index
        # backend + Stage-2 re-ranker). Masked batches bypass the cache
        # entirely — a cached decision computed without a mask must never
        # answer a masked request. Lookups are judged against the live pair
        # (db.table_version is the documented racy int read; every served
        # entry's stamps are re-verified below) and probe with raw
        # pre-adapter embeddings, so the stage_version stamp covers adapter
        # promotions too.
        cache = self._cache
        use_cache = cache is not None and candidate_masks is None
        if use_cache:
            with spans.span("route.cache"):
                tv_live = self.db.table_version
                cached = cache.lookup_batch(
                    q, table_version=tv_live, stage_version=stage_version
                )
                # tripwire, independent of the cache's own stamp check: any
                # entry whose versions differ from the live pair is demoted
                # to a miss (never served) and counted —
                # route_cache_stale_served_total must stay 0 (cache_staleness
                # SLO; benchmarks/cache_bench.py gates it in CI)
                stale = 0
                for j, e in enumerate(cached):
                    if e is not None and (
                        e.table_version != tv_live
                        or e.stage_version != stage_version
                    ):
                        cached[j] = None
                        stale += 1
                if stale and obs is not None:
                    obs.cache_stale.inc(stale)
                miss_idx = [j for j, e in enumerate(cached) if e is None]
        else:
            cached = []
            miss_idx = list(range(n_q))
        n_miss = len(miss_idx)
        # swap_table asserts the table shape is invariant, so the tool count
        # is stable across versions and safe to read without a snapshot
        n_t = len(self.db)
        rerank = stages.has_reranker
        c = min(self.k * self.candidate_multiplier, n_t) if rerank else min(self.k, n_t)
        k_eff = min(self.k, c)  # tables smaller than k yield short results
        if n_miss:
            # the scoring path sees only the miss rows: a mostly-hit batch
            # pays the index backend and re-ranker for its misses alone
            with spans.span("route.pad"):
                if n_miss == n_q:
                    q_miss, queries_miss, masks_miss = q, queries, candidate_masks
                else:
                    q_miss = q[miss_idx]
                    queries_miss = [queries[j] for j in miss_idx]
                    masks_miss = None  # masked batches never reach this branch
                # pad the miss block up to a power-of-two bucket so the
                # jitted scoring programs compile once per bucket, not once
                # per distinct Q (the scheduler's admission batches vary with
                # free slots; a retrace is a multi-ms stall against the 10 ms
                # budget). Pad rows are zero queries whose results are sliced
                # away below.
                n_pad = pad_amount(n_miss)
                if n_pad:
                    q_in = np.concatenate(
                        [q_miss, np.zeros((n_pad, q.shape[1]), np.float32)]
                    )
                    queries_in = list(queries_miss) + [np.zeros(0, np.int64)] * n_pad
                    masks_in = None if masks_miss is None else np.concatenate(
                        [masks_miss, np.ones((n_pad, n_t), masks_miss.dtype)]
                    )
                else:
                    q_in, queries_in, masks_in = q_miss, queries_miss, masks_miss
            # adapter head (query-side only) runs BEFORE the index backend —
            # the tool table is untouched, so any built IVF/Pallas index
            # stays valid across adapter promotions — and on the PADDED
            # block, so the jitted head compiles once per power-of-two
            # bucket like the scoring path (a retrace per distinct Q is a
            # multi-ms stall against the budget). pool_selector below keeps
            # seeing the raw encoder embedding `q`: pool affinity must not
            # flip on stage promotions/demotions.
            if stages.has_adapter:
                with spans.span("route.adapter"):
                    q_in = stages.adapt_queries(q_in)
            # the index layer scores the batch against an atomic
            # (version, table) snapshot — the reported table_version and
            # the scores come from the SAME table even if swap_table lands
            # mid-batch, whichever backend (or the exact mid-rebuild
            # fallback) served it. Bound to this thread for the call, the
            # recorder takes the index layer's step spans and bytes.
            with spans.span("route.score"), spans.bound():
                cand_scores_np, cand_idx_np, table_version = self.index.topk(
                    q_in, c, masks_in
                )
            if rerank:
                with spans.span("route.rerank"):
                    feats = stages.featurizer.features(
                        q_in, queries_in, cand_idx_np, cand_scores_np
                    )
                    top_idx, top_scores = reranker_lib.rerank_topk_scored(
                        stages.mlp_params,
                        jnp.asarray(feats),
                        jnp.asarray(cand_idx_np),
                        k_eff,
                        valid=jnp.asarray(cand_scores_np > NEG_INF / 2),
                    )
                    top_idx = np.asarray(top_idx)[:n_miss]
                    top_scores = np.asarray(top_scores)[:n_miss]
            else:
                top_idx = cand_idx_np[:n_miss, :k_eff]
                top_scores = cand_scores_np[:n_miss, :k_eff]
        else:
            # every row hit: the adapter, index backend, and re-ranker are
            # all skipped, and the batch reports the live pair the hits
            # were verified against
            table_version = tv_live
            top_idx = np.zeros((0, k_eff), np.int64)
            top_scores = np.zeros((0, k_eff), np.float32)
        with spans.span("route.assemble") as assemble:
            latency_ms = clock.duration_ms(t0) / n_q
            # a mask can leave fewer than k candidates; those slots carry
            # the NEG_INF sentinel and must not surface as selected tools
            miss_tools: List[List[int]] = []
            miss_scores: List[List[float]] = []
            for m in range(n_miss):
                valid_m = top_scores[m] > NEG_INF / 2
                miss_tools.append([int(t) for t in top_idx[m][valid_m]])
                miss_scores.append([float(s) for s in top_scores[m][valid_m]])
            if use_cache and n_miss:
                # fresh decisions enter the cache stamped with the versions
                # that actually produced them: the topk snapshot's
                # table_version plus the batch's stage snapshot — NOT
                # tv_live, which a mid-batch swap may already have left
                # behind
                cache.insert_batch(
                    q_miss, miss_tools, miss_scores,
                    table_version=table_version, stage_version=stage_version,
                )
            out = []
            m = 0
            for j in range(n_q):
                e = cached[j] if use_cache else None
                if e is not None:
                    tools, scores = list(e.tools), list(e.scores)
                    tv_j, hit = e.table_version, True
                else:
                    tools, scores = miss_tools[m], miss_scores[m]
                    tv_j, hit = table_version, False
                    m += 1
                out.append(
                    RouteResult(
                        tools=tools,
                        scores=scores,
                        latency_ms=latency_ms,
                        pool=self.pool_selector(q[j], tools),
                        table_version=tv_j,
                        stage_version=stage_version,
                        cache_hit=hit,
                    )
                )
        if timed:
            # the gateway's own telemetry is a span too: route_obs_ms,
            # recorded after it closes, times everything but itself
            with spans.span("route.telemetry") as telemetry:
                self._record_batch(
                    spans, tracing, n_q,
                    # the tokens the embed phase gathered: its cost per batch
                    n_tokens=sum(map(len, queries)),
                    # the bucket is what the jitted programs compiled for:
                    # the padded MISS block (an all-hit batch never reached
                    # them and reports bucket 0 under path "cache")
                    bucket=(n_miss + n_pad) if n_miss else 0,
                    path="cache" if not n_miss else self.index.last_path(),
                    table_version=table_version,
                    stage_version=stage_version,
                    total_ms=(assemble.t1 - t0) * 1e3,
                    top_scores=top_scores,
                )
            if obs is not None:
                obs.obs_ms.record(telemetry.ms)
        if self._quality is not None:
            # raw pre-adapter embeddings, unpadded rows: drift is about the
            # query population vs the live table, not about learned stages
            self._quality.observe_queries(q)
        return out

    def _record_batch(
        self,
        spans: SpanRecorder,
        tracing: bool,
        n_q: int,
        n_tokens: int,
        bucket: int,
        path: str,
        table_version: int,
        stage_version: int,
        total_ms: float,
        top_scores: np.ndarray,
    ) -> None:
        """One batch's spans into its sampled trace and the histograms."""
        obs = self._obs
        # trace BEFORE metrics: a sampled batch's trace id becomes the
        # exemplar on the duration buckets it lands in, so a p99 reading
        # links straight to a concrete RouteTrace ("/slo" and
        # `repro-obs watch` render that link)
        trace = None
        if tracing:
            trace = self._tracer.record(
                batch_size=n_q,
                bucket=bucket,
                path=path,
                table_version=table_version,
                stage_version=stage_version,
                spans=[(name.removeprefix("route."), ms) for name, ms in spans.spans],
                total_ms=total_ms,
                ts=spans.entry_wall(),
            )
        if obs is None:
            return
        exemplar = trace.trace_id if trace is not None else None
        obs.requests.inc(n_q)
        obs.tokens.inc(n_tokens)
        obs.batches.inc()
        obs.batch_size.record(float(n_q))
        obs.batch_ms.record(total_ms, exemplar=exemplar)
        phase = obs.phase
        for name, ms in spans.under("route."):
            phase[name].record(ms, exemplar=exemplar)
        step = obs.index_step
        for name, ms in spans.under("index."):
            step[name].record(ms)
        if spans.h2d_copies:
            obs.transfer_bytes["h2d"].inc(spans.h2d_bytes)
            obs.transfer_bytes["d2h"].inc(spans.d2h_bytes)
            obs.transfers["h2d"].inc(spans.h2d_copies)
            obs.transfers["d2h"].inc(spans.d2h_copies)
        if top_scores.shape[1] >= 2:
            # sampled 1-in-4 batches: the gap histogram feeds percentile
            # summaries (confidence()), which a quarter of the traffic
            # estimates as well as all of it — and this is the priciest
            # per-batch obs block (a vectorized pass + record_many). Racy
            # tick increment is fine: the sampling needs to be approximate,
            # not exact.
            self._gap_tick += 1
            if self._gap_tick % 4 == 0:
                # rows with < 2 valid candidates carry the NEG_INF sentinel
                # in slot 1 and are skipped
                valid2 = top_scores[:, 1] > NEG_INF / 2
                if np.any(valid2):
                    gaps = top_scores[:, 0] - top_scores[:, 1]
                    obs.score_gap.record_many(gaps[valid2])

    def route(
        self,
        query_tokens: np.ndarray,
        candidate_mask: Optional[np.ndarray] = None,  # [T] {0,1} or None
    ) -> RouteResult:
        """Single-query routing: the batch-of-1 case of `route_batch`."""
        masks = None if candidate_mask is None else np.asarray(candidate_mask)[None]
        return self.route_batch([query_tokens], masks)[0]

    # ------------------------------------------------------------ feedback
    def record_outcome(self, query_tokens: np.ndarray, tool_id: int, outcome: int):
        event = OutcomeEvent(
            query_tokens=query_tokens,
            tool_id=tool_id,
            outcome=int(outcome),
            timestamp=clock.wall(),
        )
        if self.outcome_sink is not None:
            self.outcome_sink(event)
            return
        n_dropped = 0
        with self._outcome_lock:
            if len(self.outcome_log) >= self.outcome_capacity:
                self.outcome_log.popleft()
                self.outcomes_dropped += 1
                n_dropped = self.outcomes_dropped
            self.outcome_log.append(event)
        if n_dropped:
            # counter + bus outside the ring lock: telemetry must not extend
            # the record/drain critical section
            if self._obs is not None:
                self._obs.outcomes_dropped.inc()
            if self._bus is not None and n_dropped == 1:
                self._bus.publish("outcomes_dropping", plane="serve",
                                  dropped=n_dropped)

    def drain_outcomes(self) -> List[OutcomeEvent]:
        """Hand the accumulated log to the offline refinement job."""
        with self._outcome_lock:
            log = list(self.outcome_log)
            self.outcome_log.clear()
        return log
