"""Route cache in front of the gateway, end to end, on real Zipf traffic.

  PYTHONPATH=src python examples/cached_gateway.py [--n-tools N] [--batches N]

Production traffic is not i.i.d.: a few intents dominate ("what's the
weather", "summarize this") and most arrivals are near-duplicates of
something routed seconds ago. This demo builds the same serving stack as
`launch/serve.py --route-cache`, but small and inline so every moving part
is visible:

  1. a 6k-tool corpus behind a `SemanticRouter`;
  2. a `SemanticRouteCache` attached to it (LSH probe in embedding space,
     cosine threshold 0.95, every entry stamped with the live
     `(table_version, stage_version)` pair);
  3. a seeded Zipfian near-duplicate stream (`repro.traffic`) replayed
     through a bare router and the cached one — identical queries, so the
     printed agreement is a real routing-decision comparison;
  4. a mid-stream control-plane swap, to show the version-stamp discipline:
     the swap bumps `table_version`, the whole cache goes cold (watch the
     `cache_invalidated` event), hit-rate dips and recovers, and the
     staleness gate in `repro.traffic.drive` confirms nothing was served
     from the dead snapshot.

`tests/test_cache.py` holds the cache's correctness (zero stale serves
under churn; hit rate and agreement on the seeded Zipf-1.1 stream); this
is the 30-second tour.
"""
import argparse

import numpy as np

from repro.cache import CacheConfig, SemanticRouteCache
from repro.data.benchmarks import make_metatool_like, scale_tool_corpus
from repro.embedding.bag_encoder import BagEncoder
from repro.obs import EventBus
from repro.router.gateway import SemanticRouter
from repro.router.tooldb import ToolRecord, ToolsDatabase
from repro.traffic import TrafficConfig, ZipfTrafficGenerator, agreement, drive

QUERY_LEN = 24  # tiled intent length: 1-token jitter keeps cosine ~0.958


def build_router(n_tools: int, cache, bus=None):
    bench = make_metatool_like(seed=0, n_queries=400)
    enc = BagEncoder(bench.vocab)
    table = scale_tool_corpus(enc.encode(bench.desc_tokens), n_tools,
                              seed=0, noise=0.2)
    records = [ToolRecord(i, f"t{i}", bench.desc_tokens[i % bench.n_tools], 0)
               for i in range(n_tools)]
    db = ToolsDatabase(records, table)
    router = SemanticRouter(db, embed_fn=enc.encode_one,
                            embed_batch_fn=enc.encode, k=5,
                            metrics=False, cache=cache)
    if cache is not None and bus is not None:
        bus.watch_db(db)  # db publishes swap/rollback lifecycle events...
        cache.watch(bus)  # ...and the cache eagerly purges on each one
    # pool of real train-split intents, token-tiled to QUERY_LEN so the
    # bag-encoder direction is preserved exactly
    pool = [np.tile(t, -(-QUERY_LEN // len(t)))
            for t in (bench.query_tokens[i] for i in bench.train_idx)]
    return router, pool


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n-tools", type=int, default=6000)
    ap.add_argument("--batches", type=int, default=60)
    args = ap.parse_args(argv)

    bus = EventBus()
    cache = SemanticRouteCache(CacheConfig(threshold=0.95), metrics=False,
                               bus=bus)
    cached, pool = build_router(args.n_tools, cache, bus=bus)
    bare, _ = build_router(args.n_tools, None)

    cfg = TrafficConfig(zipf_s=1.1, pool_size=256, query_len=QUERY_LEN,
                        batch_size=32, paraphrase_p=0.35, jitter_tokens=1,
                        seed=3)
    batches = list(ZipfTrafficGenerator(cfg, pool=pool).stream(args.batches))

    # compile every pow2 miss-bucket shape once, then forget the warmup
    for m in (1, 2, 4, 8, 16, 32):
        cached.route_batch(batches[0][:m])
        bare.route_batch(batches[0][:m])
    cache.clear()

    # fire one content-identical table swap a third of the way in: the
    # version bump MUST invalidate the cache without changing routing
    swap_at = max(1, args.batches // 3)

    def churn(i: int) -> None:
        if i == swap_at:
            version, live = cached.db.snapshot()
            cached.db.swap_table(live.copy(), expect_current=version)

    try:
        rep_c = drive(cached, batches, record=True, on_batch=churn)
        rep_b = drive(bare, batches, record=True)
    finally:
        cached.close()
        bare.close()

    agr = agreement(rep_c.results, rep_b.results)
    purges = bus.events(kind="cache_invalidated")
    print(f"tools={args.n_tools}  batches={rep_c.batches}  "
          f"queries={rep_c.queries}")
    print(f"cached: {rep_c.qps:8.0f} qps  p50={rep_c.p50_ms:5.2f}ms  "
          f"p99={rep_c.p99_ms:5.2f}ms  hit_rate={rep_c.hit_rate:.3f}")
    print(f"bare:   {rep_b.qps:8.0f} qps  p50={rep_b.p50_ms:5.2f}ms  "
          f"p99={rep_b.p99_ms:5.2f}ms")
    print(f"speedup {rep_c.qps / rep_b.qps:.2f}x at top-1 agreement {agr:.4f}")
    print(f"swap at batch {swap_at}: {len(purges)} cache_invalidated "
          f"event(s), {cache.stats['invalidated']} entries purged, "
          f"stale serves {rep_c.stale_serves} (must be 0)")
    return 1 if rep_c.stale_serves else 0


if __name__ == "__main__":
    raise SystemExit(main())
