"""Route-cache tests: LSH/LRU/invalidation unit behavior, gateway
integration (hit correctness, mask bypass, mixed batches, swap and stage
invalidation), and the threaded churn race the version stamps exist for —
no stale result may ever be served while swaps/rollbacks/promotions land
concurrently with routing, and the hit rate must recover afterwards — and
the warm hit rate and top-1 agreement on a seeded Zipf-1.1 stream."""
import threading

import numpy as np
import pytest

from repro.cache import CacheConfig, CachedRoute, SemanticRouteCache
from repro.obs.events import EventBus
from repro.obs.metrics import MetricsRegistry
from repro.router.gateway import SemanticRouter
from repro.router.tooldb import ToolRecord, ToolsDatabase

D = 32


def _embed(tokens):
    v = np.bincount(np.asarray(tokens, np.int64) % D, minlength=D).astype(np.float32)
    n = np.linalg.norm(v)
    return v / n if n else v


def _embed_batch(token_lists):
    return np.stack([_embed(t) for t in token_lists])


def _unit(rng, n=1):
    v = rng.standard_normal((n, D)).astype(np.float32)
    return v / np.linalg.norm(v, axis=1, keepdims=True)


def _make_router(n_tools=24, cache=None, metrics=False, bus=None):
    rng = np.random.default_rng(0)
    records = [ToolRecord(i, f"t{i}", np.arange(3), 0) for i in range(n_tools)]
    table = rng.standard_normal((n_tools, D)).astype(np.float32)
    table /= np.linalg.norm(table, axis=1, keepdims=True)
    db = ToolsDatabase(records, table)
    router = SemanticRouter(
        db, embed_fn=_embed, embed_batch_fn=_embed_batch, k=3,
        cache=cache, metrics=metrics, bus=bus,
    )
    return router, db


def _queries(rng, n, lo=0, hi=200):
    return [rng.integers(lo, hi, size=8).astype(np.int64) for _ in range(n)]


# ------------------------------------------------------------------ unit


def test_insert_then_lookup_hits_with_stamps():
    cache = SemanticRouteCache(CacheConfig(threshold=0.95), metrics=False)
    rng = np.random.default_rng(1)
    q = _unit(rng, 3)
    cache.insert_batch(q, [[1, 2], [3, 4], [5, 6]],
                       [[0.9, 0.5], [0.8, 0.4], [0.7, 0.3]],
                       table_version=7, stage_version=2)
    out = cache.lookup_batch(q, table_version=7, stage_version=2)
    assert all(e is not None for e in out)
    assert out[0].tools == (1, 2) and out[0].scores == (0.9, 0.5)
    assert out[0].table_version == 7 and out[0].stage_version == 2
    # a mild perturbation (cosine ~0.995) still hits the same entries
    near = q + 0.05 * _unit(rng, 3)
    near /= np.linalg.norm(near, axis=1, keepdims=True)
    hits = cache.lookup_batch(near, table_version=7, stage_version=2)
    assert sum(e is not None for e in hits) >= 2  # LSH recall is probabilistic
    # an unrelated direction misses
    far = cache.lookup_batch(_unit(rng, 1), table_version=7, stage_version=2)
    assert far == [None]
    assert cache.hit_rate() > 0.0


def test_stamp_mismatch_is_never_served_and_reclaims():
    cache = SemanticRouteCache(metrics=False)
    rng = np.random.default_rng(2)
    q = _unit(rng, 1)
    cache.insert_batch(q, [[1]], [[0.9]], table_version=1, stage_version=1)
    assert len(cache) == cache.config.n_tables
    # either version moving makes the entry dead — and lookup purges it
    assert cache.lookup_batch(q, table_version=2, stage_version=1) == [None]
    assert len(cache) == 0
    cache.insert_batch(q, [[1]], [[0.9]], table_version=2, stage_version=1)
    assert cache.lookup_batch(q, table_version=2, stage_version=2) == [None]
    assert cache.stats["invalidated"] == 2 * cache.config.n_tables


def test_threshold_two_is_supported_never_hit_mode():
    cache = SemanticRouteCache(CacheConfig(threshold=2.0), metrics=False)
    q = _unit(np.random.default_rng(3), 2)
    cache.insert_batch(q, [[1], [2]], [[0.9], [0.8]],
                       table_version=1, stage_version=1)
    # even a byte-identical duplicate misses: cosine 1.0 < 2.0
    out = cache.lookup_batch(q, table_version=1, stage_version=1)
    assert out == [None, None]
    assert cache.stats["misses"] == 2 and cache.stats["hits"] == 0


def test_min_gap_guards_near_tie_decisions():
    cache = SemanticRouteCache(CacheConfig(min_gap=0.05), metrics=False)
    rng = np.random.default_rng(4)
    q = _unit(rng, 2)
    cache.insert_batch(q, [[1, 2], [3, 4]],
                       [[0.90, 0.89], [0.90, 0.70]],  # gaps 0.01 and 0.20
                       table_version=1, stage_version=1)
    out = cache.lookup_batch(q, table_version=1, stage_version=1)
    assert out[0] is None  # near-tie: scored fresh
    assert out[1] is not None and out[1].gap == pytest.approx(0.20)


def test_lru_eviction_bounds_capacity():
    cfg = CacheConfig(n_tables=4, capacity=16)  # 4 distinct decisions
    cache = SemanticRouteCache(cfg, metrics=False)
    rng = np.random.default_rng(5)
    for i in range(10):
        cache.insert_batch(_unit(rng, 1), [[i]], [[0.5]],
                           table_version=1, stage_version=1)
    assert len(cache) <= cfg.capacity
    assert cache.stats["evictions"] > 0


def test_invalidate_and_watch_purge_eagerly():
    bus = EventBus()
    registry = MetricsRegistry()
    cache = SemanticRouteCache(metrics=registry, bus=bus)
    detach = cache.watch(bus)
    rng = np.random.default_rng(6)
    cache.insert_batch(_unit(rng, 2), [[1], [2]], [[0.9], [0.8]],
                       table_version=1, stage_version=0)
    # a table swap event purges everything stamped with the old version
    bus.publish("swap", plane="control", version=2)
    assert len(cache) == 0
    assert registry.counter("route_cache_invalidated_total").value() > 0
    events = bus.events(kind="cache_invalidated")
    assert events and events[-1].details["purged"] == 2 * cache.config.n_tables
    assert events[-1].details["reason"] == "swap"
    # stage events purge by the stage stamp
    cache.insert_batch(_unit(rng, 1), [[3]], [[0.9]],
                       table_version=2, stage_version=0)
    bus.publish("stage_swap", plane="learn", version=1)
    assert len(cache) == 0
    detach()
    cache.insert_batch(_unit(rng, 1), [[4]], [[0.9]],
                       table_version=2, stage_version=1)
    bus.publish("swap", plane="control", version=3)
    assert len(cache) > 0  # detached: no eager purge (stamps still protect)


# ------------------------------------------------------------- integration


def test_gateway_serves_identical_results_from_cache():
    cache = SemanticRouteCache(metrics=False)
    router, _ = _make_router(cache=cache)
    qs = _queries(np.random.default_rng(7), 4)
    first = router.route_batch(qs)
    second = router.route_batch(qs)
    assert all(not r.cache_hit for r in first)
    assert all(r.cache_hit for r in second)
    for a, b in zip(first, second):
        assert a.tools == b.tools
        assert np.allclose(a.scores, b.scores)
        assert (b.table_version, b.stage_version) == (
            a.table_version, a.stage_version)
    router.close()


def test_gateway_masked_batches_bypass_cache():
    cache = SemanticRouteCache(metrics=False)
    router, db = _make_router(cache=cache)
    qs = _queries(np.random.default_rng(8), 2)
    router.route_batch(qs)  # warm the cache
    before = dict(cache.stats)
    masks = np.ones((2, len(db)), dtype=np.float32)
    masked = router.route_batch(qs, candidate_masks=masks)
    assert all(not r.cache_hit for r in masked)
    assert cache.stats == before  # never probed, never inserted
    router.close()


def test_gateway_mixed_hit_miss_batch_preserves_order():
    cache = SemanticRouteCache(metrics=False)
    router, _ = _make_router(cache=cache)
    rng = np.random.default_rng(9)
    qs = _queries(rng, 3)
    baseline = router.route_batch(qs)  # inserts all three
    fresh = _queries(rng, 2, lo=300, hi=900)
    mixed = router.route_batch([qs[1], fresh[0], qs[2], fresh[1]])
    assert [r.cache_hit for r in mixed] == [True, False, True, False]
    assert mixed[0].tools == baseline[1].tools
    assert mixed[2].tools == baseline[2].tools
    # the misses were really scored: they carry k tools with finite scores
    assert len(mixed[1].tools) == router.k
    router.close()


def test_swap_and_stage_bump_invalidate_lazily():
    cache = SemanticRouteCache(metrics=False)
    router, db = _make_router(cache=cache)
    qs = _queries(np.random.default_rng(10), 2)
    router.route_batch(qs)
    assert all(r.cache_hit for r in router.route_batch(qs))
    # content-identical table swap: routing unchanged, version moved —
    # every cached decision must be re-scored, results must agree
    version, live = db.snapshot()
    db.swap_table(live.copy(), expect_current=version)
    post = router.route_batch(qs)
    assert all(not r.cache_hit for r in post)
    assert all(r.table_version == db.table_version for r in post)
    assert all(r.cache_hit for r in router.route_batch(qs))  # re-warmed
    # stage bump (re-deploying the same StageSet) invalidates the same way
    sv, stages = router.stage_set()
    router.set_stages(stages, expect_version=sv)
    post_stage = router.route_batch(qs)
    assert all(not r.cache_hit for r in post_stage)
    assert all(r.stage_version == router.stage_version for r in post_stage)
    router.close()


def test_threaded_churn_never_serves_stale_and_recovers():
    registry = MetricsRegistry()
    cache = SemanticRouteCache(metrics=registry)
    router, db = _make_router(cache=cache, metrics=registry)
    rng = np.random.default_rng(11)
    pools = [_queries(rng, 4) for _ in range(6)]
    stop = threading.Event()
    violations = []

    def serve(worker: int):
        i = 0
        while not stop.is_set() or i < 20:
            batch = pools[(i + worker) % len(pools)]
            tv0, sv0 = db.table_version, router.stage_version
            results = router.route_batch(batch)
            tv1, sv1 = db.table_version, router.stage_version
            for r in results:
                if not (tv0 <= r.table_version <= tv1
                        and sv0 <= r.stage_version <= sv1):
                    violations.append(
                        (worker, r.table_version, r.stage_version,
                         (tv0, sv0), (tv1, sv1)))
            i += 1
            if i >= 300:
                break

    workers = [threading.Thread(target=serve, args=(w,)) for w in range(3)]
    for t in workers:
        t.start()
    # control-plane churn from the main thread: swaps, rollbacks, stage
    # promotions — all content-identical, so any disagreement is a cache bug
    for step in range(30):
        if step % 3 == 0:
            version, live = db.snapshot()
            db.swap_table(live.copy(), expect_current=version)
        elif step % 3 == 1 and db.retained_versions():
            db.rollback(expect_current=db.table_version)
        else:
            sv, stages = router.stage_set()
            router.set_stages(stages, expect_version=sv)
    stop.set()
    for t in workers:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in workers)
    assert violations == []
    # the gateway tripwire never demoted a hit either: the cache's own
    # stamp check caught every dead entry first
    assert registry.counter("route_cache_stale_served_total").value() == 0
    # and the cache still works: hit rate recovers once churn stops
    qs = pools[0]
    router.route_batch(qs)
    assert all(r.cache_hit for r in router.route_batch(qs))
    router.close()


def test_zipf_stream_hit_rate_floor_and_agreement_with_bare_router():
    """On the seeded Zipf-1.1 near-duplicate stream over a 25k-tool corpus,
    the warm cache serves at least 90% of queries, agrees with a bare
    router's top-1 on at least 98% of them, and serves nothing stale."""
    from repro.data.benchmarks import make_metatool_like, scale_tool_corpus
    from repro.embedding.bag_encoder import BagEncoder
    from repro.traffic import TrafficConfig, ZipfTrafficGenerator, agreement, drive

    bench = make_metatool_like(seed=0, n_queries=400)
    enc = BagEncoder(bench.vocab)
    # noise 0.2 keeps the clones inert decoys: top-1 competition stays
    # among the real tools, so agreement measures the cache, not clones
    table = scale_tool_corpus(enc.encode(bench.desc_tokens), 25_000,
                              seed=0, noise=0.2)
    records = [ToolRecord(i, f"t{i}", bench.desc_tokens[i % bench.n_tools], 0)
               for i in range(len(table))]
    # train queries tiled to 24 tokens: the bag direction is unchanged and
    # a one-token paraphrase stays inside the cache's cosine threshold
    pool = [np.tile(t, -(-24 // len(t)))
            for t in (bench.query_tokens[i] for i in bench.train_idx)]
    cfg = TrafficConfig(zipf_s=1.1, pool_size=256, query_len=24,
                        batch_size=32, paraphrase_p=0.35, jitter_tokens=1,
                        seed=3)
    batches = list(ZipfTrafficGenerator(cfg, pool=pool).stream(150))

    def router(cache):
        return SemanticRouter(
            ToolsDatabase(list(records), table.copy()),
            embed_fn=enc.encode_one, embed_batch_fn=enc.encode, k=5,
            metrics=MetricsRegistry(), cache=cache,
        )

    cached = router(SemanticRouteCache(CacheConfig(threshold=0.95),
                                       metrics=MetricsRegistry()))
    bare = router(None)
    try:
        rep_c = drive(cached, batches, record=True)
        rep_b = drive(bare, batches, record=True)
    finally:
        cached.close()
        bare.close()
    assert rep_c.stale_serves == rep_b.stale_serves == 0
    assert rep_b.hit_rate == 0.0
    assert rep_c.hit_rate >= 0.90, rep_c.hit_rate
    assert agreement(rep_c.results, rep_b.results) >= 0.98


def test_cache_metrics_exported_through_gateway():
    registry = MetricsRegistry()
    cache = SemanticRouteCache(metrics=registry)
    router, _ = _make_router(cache=cache, metrics=registry)
    qs = _queries(np.random.default_rng(12), 3)
    router.route_batch(qs)
    router.route_batch(qs)
    assert registry.counter("route_cache_hits_total").value() == 3
    assert registry.counter("route_cache_misses_total").value() == 3
    assert registry.gauge("route_cache_hit_ratio").value() == pytest.approx(0.5)
    assert registry.gauge("route_cache_size").value() == len(cache)
    # the cache phase span was recorded for both batches
    hist = registry.histogram("route_phase_ms", phase="cache")
    assert hist.count() == 2
    router.close()
