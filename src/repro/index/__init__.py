"""Tool-index subsystem: pluggable similarity-scoring backends behind
`SemanticRouter.route_batch` (PR 3).

The paper keeps tool selection in the request path on a single-digit-ms CPU
budget; this package is what lets that hold as the tool table grows from
the paper's 2,413 entries to MCP-registry scale (25k-100k). Scoring is a
`ScorerBackend` built from one table snapshot, and `ToolIndexManager` keeps
the index consistent with the PR 2 swap/rollback protocol (exact fallback
while a rebuild is in flight — see `manager.py`).

Backend-selection guide
=======================

``dense`` — `DenseBackend` (default)
    Exact brute force: one jitted matmul + `lax.top_k`, candidate masks
    supported natively. Per-query cost O(T·D). Pick it when T is small
    (≲ 10k tools: on this CPU the whole batch scores in well under the
    budget), when results must be bit-exact (it is the oracle every other
    backend is validated against), or when queries carry candidate masks.
    Zero build cost beyond a device upload, so swap churn is nearly free.

``ivf`` — `IVFBackend`
    k-means coarse quantization: score C ≈ 4·√T centroids, visit the
    `nprobe` closest clusters, shortlist members with int8 codes
    (`models/quant` machinery), exact-re-rank the shortlist in fp32.
    Per-query cost O(C·D + nprobe·(T/C)·D) — at 100k tools ~60x less
    arithmetic than dense. Pick it when T ≳ 25k and approximate recall is
    acceptable (Recall@5 ≥ 0.98 vs exact at the default `nprobe=8`;
    raise `nprobe` to trade latency for recall). Builds take seconds at
    100k tools, so sustained swap churn serves through the exact fallback
    between rebuilds. No candidate-mask support (masked batches fall back).

``pallas`` — `PallasBackend`
    The fused score+top-K Pallas kernel (`kernels/topk_sim`): streams the
    table HBM→VMEM in tiles with a running top-K in scratch — exact
    results, no [Q, T] score matrix materialized. Pick it on TPU-backed
    routers at any scale where dense's HBM traffic is the bottleneck. On
    CPU it transparently serves the jnp reference (same numerics as
    ``dense``); `interpret=True` executes the kernel body on CPU for tests
    only. No candidate-mask support (masked batches fall back).

`tests/test_index.py` holds IVF's Recall@5 >= 0.98 against the exact
dense top-K at the default ``nprobe``. Speed is measured on the chip by
the benchmark's cells (`score_ms`, `score_roofline`; `bench/`), which
today run ``dense`` only.
"""
from repro.index.base import NEG_INF, ScorerBackend
from repro.index.dense import DenseBackend
from repro.index.ivf import IVFBackend, IVFConfig
from repro.index.manager import ToolIndexManager
from repro.index.pallas_backend import PallasBackend

__all__ = [
    "NEG_INF",
    "ScorerBackend",
    "DenseBackend",
    "IVFBackend",
    "IVFConfig",
    "PallasBackend",
    "ToolIndexManager",
    "BACKENDS",
    "build_backend",
]

BACKENDS = {
    DenseBackend.name: DenseBackend,
    IVFBackend.name: IVFBackend,
    PallasBackend.name: PallasBackend,
}


def build_backend(kind: str, table, table_version: int, **opts) -> ScorerBackend:
    """Construct a registered backend over one table snapshot."""
    if kind not in BACKENDS:
        raise ValueError(f"unknown backend {kind!r} (available: {sorted(BACKENDS)})")
    return BACKENDS[kind](table, table_version, **opts)
