"""Runtime jit-retrace detector for the hot serving path.

A retrace (a fresh XLA compile) is a multi-ms stall against the 10 ms p99
budget, so `route_batch` pads every batch into a power-of-two bucket
(`repro.common.bucketing`) and the jitted entry points are supposed to
compile once per bucket, ever. This module checks that contract at
runtime: `RetraceMonitor` records each tracked jitted callable's compile
cache size (`jax.jit(f)._cache_size()`) around a workload and reports how
many NEW traces the workload caused.

Two consumers:

* `python -m repro.analysis.retrace` — CI leg: builds a small router
  (dense backend, adapter stage active), sweeps mixed batch sizes through
  `route_batch`, and fails if any hot-path entry point traced more than
  once per (power-of-two bucket x live table/stage generation);
* `tests/test_analysis.py` — runs the same scenario
  (`test_route_batch_stays_inside_bucket_set`) and the monitor's own
  contract tests.

`_cache_size` is a private-but-stable jax API (present throughout the
0.4.x line this repo pins). When a jitted callable does not expose it the
monitor degrades to "unsupported" rather than failing: the static
`jit-in-function` / `jit-static-scalar` lint rules still cover the
construction-time hazards.
"""
from __future__ import annotations

import argparse
import sys
from typing import Callable, Dict, List, Optional

import numpy as np

__all__ = ["supports_cache_size", "RetraceMonitor", "hot_path_monitor", "main"]


def supports_cache_size(fn) -> bool:
    """True when `fn` exposes the jit compile-cache probe this module needs."""
    return callable(getattr(fn, "_cache_size", None))


class RetraceMonitor:
    """Counts new jit traces per tracked callable across a workload.

    Usage::

        mon = RetraceMonitor()
        mon.track("topk_dense", retrieval.topk_dense)
        with mon:
            run_workload()
        mon.check({"topk_dense": expected_max_traces})  # -> violations

    Counting deltas (not absolute cache sizes) makes the monitor
    composable with anything that already warmed the cache — a prior test,
    a warmup sweep — at the cost of missing traces that happened before
    `__enter__`. CI runs it around the FULL workload in a fresh process,
    where the delta is the absolute count.
    """

    def __init__(self):
        self._fns: Dict[str, Callable] = {}
        self._unsupported: List[str] = []
        self._before: Dict[str, int] = {}
        self._after: Optional[Dict[str, int]] = None

    def track(self, name: str, fn: Callable) -> bool:
        """Register a jitted callable; False (and skip) if unsupported."""
        if not supports_cache_size(fn):
            self._unsupported.append(name)
            return False
        self._fns[name] = fn
        return True

    @property
    def unsupported(self) -> List[str]:
        return list(self._unsupported)

    def __enter__(self):
        self._after = None
        self._before = {n: f._cache_size() for n, f in self._fns.items()}
        return self

    def __exit__(self, *exc):
        self._after = {n: f._cache_size() for n, f in self._fns.items()}
        return False

    def traces(self) -> Dict[str, int]:
        """{name: new traces during the with-block}."""
        assert self._after is not None, "traces() outside a completed with-block"
        return {n: self._after[n] - self._before[n] for n in self._fns}

    def check(self, budget: Dict[str, int]) -> List[str]:
        """Human-readable violations for every tracked fn over its budget."""
        got = self.traces()
        out = []
        for name, limit in budget.items():
            if name not in got:
                continue  # unsupported or untracked: not a failure
            if got[name] > limit:
                out.append(
                    f"{name}: {got[name]} traces > expected {limit} — a "
                    f"batch escaped the power-of-two buckets (or a new "
                    f"shape/dtype generation was introduced silently)"
                )
        return out


def hot_path_monitor() -> RetraceMonitor:
    """Monitor pre-loaded with the route_batch hot-path entry points.

    Sourced from `repro.router.gateway.hot_path_jits` — the gateway owns
    the list, so this CI leg and the live `obs.profile.JitProfiler` can
    never silently watch different program sets.
    """
    from repro.router.gateway import hot_path_jits

    mon = RetraceMonitor()
    for name, fn in hot_path_jits().items():
        mon.track(name, fn)
    return mon


# ----------------------------------------------------------------- CI leg


def _build_router(n_tools: int, dim: int, seed: int):
    """Small self-contained router: dense backend + adapter stage active."""
    import jax
    import jax.numpy as jnp

    from repro.router.gateway import SemanticRouter
    from repro.router.stages import StageSet
    from repro.router.tooldb import ToolRecord, ToolsDatabase

    rng = np.random.default_rng(seed)
    emb = rng.normal(size=(n_tools, dim)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    records = [
        ToolRecord(i, f"tool_{i}", np.arange(1, dtype=np.int64), 0)
        for i in range(n_tools)
    ]
    db = ToolsDatabase(records, emb)

    def embed_one(tokens: np.ndarray) -> np.ndarray:
        v = np.sin((np.arange(dim) + 1.0) * (1.0 + float(np.sum(tokens))))
        return (v / np.linalg.norm(v)).astype(np.float32)

    def embed_batch(batch) -> np.ndarray:
        return np.stack([embed_one(t) for t in batch])

    # a dim-matched residual head (init_adapter is pinned to the production
    # 384-dim encoder; the scenario uses a small dim to keep CI fast). Same
    # structure as repro.core.adapter: identity at w2=0, so routing quality
    # is irrelevant — only the compile-cache behavior is under test.
    hidden = max(dim // 2, 2)
    k1 = jax.random.PRNGKey(seed + 1)
    params = {
        "w1": jax.random.normal(k1, (dim, hidden), jnp.float32) * 0.02,
        "b1": jnp.zeros((hidden,), jnp.float32),
        "w2": jnp.zeros((hidden, dim), jnp.float32),
        "b2": jnp.zeros((dim,), jnp.float32),
    }
    router = SemanticRouter(
        db,
        embed_fn=embed_one,
        embed_batch_fn=embed_batch,
        k=4,
        stages=StageSet(adapter_params=params, adapter_scale=0.1),
    )
    return router, db


def run_scenario(
    batch_sizes, n_tools: int = 48, dim: int = 16, seed: int = 0
) -> Dict[str, object]:
    """Sweep `batch_sizes` through route_batch under the hot-path monitor.

    Returns {"traces": {...}, "violations": [...], "unsupported": [...],
    "buckets": [...]}.
    """
    from repro.common.bucketing import expected_buckets

    router, _ = _build_router(n_tools, dim, seed)
    rng = np.random.default_rng(seed + 7)
    mon = hot_path_monitor()
    try:
        with mon:
            for n in batch_sizes:
                queries = [
                    rng.integers(0, 50, size=rng.integers(1, 6)).astype(np.int64)
                    for _ in range(n)
                ]
                results = router.route_batch(queries)
                assert len(results) == n
        buckets = expected_buckets(batch_sizes)
        # one trace per bucket for every entry point on the route_batch
        # path; the reranker is not configured in this scenario so its
        # budget is zero new traces
        violations = mon.check(
            {
                "topk_dense": len(buckets),
                "adapter_apply": len(buckets),
                "rerank_topk_scored": 0,
            }
        )
    finally:
        router.close()
    return {
        "traces": mon.traces(),
        "violations": violations,
        "unsupported": mon.unsupported,
        "buckets": buckets,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.retrace",
        description="Fail if route_batch hot-path jits retrace beyond the "
        "power-of-two bucket set.",
    )
    ap.add_argument(
        "--smoke", action="store_true", help="small sweep (CI default sizes)"
    )
    ap.add_argument(
        "--batch-sizes",
        default=None,
        help="comma-separated batch sizes (overrides --smoke)",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.batch_sizes:
        sizes = [int(s) for s in args.batch_sizes.split(",") if s.strip()]
    else:
        # mixed ragged sizes sharing buckets: {1,2,3,4} -> buckets {1,2,4},
        # {5,7,8} -> {8}, {9,16} -> {16} — 6 buckets, 12 calls
        sizes = [1, 2, 3, 4, 5, 7, 8, 9, 16, 3, 8, 16]

    report = run_scenario(sizes, seed=args.seed)
    print(f"batch sizes: {sizes}")
    print(f"expected buckets: {report['buckets']}")
    for name, n in sorted(report["traces"].items()):
        print(f"  {name}: {n} trace(s)")
    for name in report["unsupported"]:
        print(f"  {name}: SKIPPED (no _cache_size on this jax build)")
    if report["violations"]:
        for v in report["violations"]:
            print(f"RETRACE VIOLATION: {v}", file=sys.stderr)
        return 1
    print("retrace check OK: hot path compiled once per bucket")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
