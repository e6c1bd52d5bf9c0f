"""Latency measurement harness (paper §5.5, Tables 1 & 6).

Measures per-request p50/p99 wall-clock on a single CPU process, covering
embedding computation, similarity search, and any re-ranking overhead —
exactly the paper's protocol. The embedding forward uses the MiniLM-shaped
22M-parameter transformer (repro.embedding.transformer), so the dominant cost
term matches the production router's, independent of weight values.

The percentile math itself lives in `repro.obs.summary` (one implementation
shared by this harness and the tracer report);
`LatencyStats`/`percentile_stats` are re-exported here for compatibility.
"""
from __future__ import annotations

from typing import Callable, List

from repro.obs import clock
from repro.obs.summary import LatencyStats, percentile_stats

__all__ = ["LatencyStats", "measure_latency", "percentile_stats"]


def measure_latency(
    serve_one: Callable[[int], object],
    n_requests: int,
    warmup: int = 20,
) -> LatencyStats:
    """Time `serve_one(i)` per request (one at a time — router semantics)."""
    for i in range(min(warmup, n_requests)):
        serve_one(i)
    samples: List[float] = []
    for i in range(n_requests):
        t0 = clock.perf()
        serve_one(i)
        samples.append(clock.duration_ms(t0))
    return percentile_stats(samples)
