"""ScorerBackend: the contract every tool-index backend serves behind.

A backend is an *immutable* index built from one atomic table snapshot: it
captures `(table_version, table)` at build time and answers batched top-K
similarity queries against exactly that table until it is replaced. All
mutability lives one layer up in `ToolIndexManager`, which owns the
build/swap lifecycle — this split is what keeps the PR 2 swap/rollback
protocol intact: a backend can never serve scores from one version while
reporting another.

Contract (`topk`):

  * input `queries` is a `[Q, D]` float32 block of unit rows (the gateway's
    padded batch); `k` is the candidate count the caller wants back;
  * output is `(scores [Q, k] float32, indices [Q, k] int)` sorted by
    descending score per row. Slots that cannot be filled (masked-out, or
    fewer than `k` reachable candidates) carry the `NEG_INF` sentinel score
    shared with `core.retrieval` — callers already filter on
    `score > NEG_INF / 2`, so short results flow through `route_batch`
    unchanged;
  * `scores` must be the scores the final ranking was computed from
    (exact fp32 similarities after any approximate shortlist), so
    `RouteResult.scores` stays meaningful across backends;
  * backends that cannot honor per-query candidate masks declare
    `supports_masks = False`; `ToolIndexManager` routes masked batches to
    the exact dense fallback instead of calling them with one.

The device backends (dense, pallas, and the manager's exact fallback, a
`DenseBackend`) share one host round trip, `round_trip`: their jitted
program returns the top-K as one packed `[Q, 2k]` int32 block
(`core.retrieval.pack_topk`), so each call makes one copy up (two with a
mask) and one copy back. The NumPy query block goes straight to the
jitted call, which copies it up as it dispatches. It times its steps as
spans of the calling batch (`repro.obs.trace.current_spans`).
"""
from __future__ import annotations

from typing import Callable, Optional, Protocol, Tuple, runtime_checkable

import jax
import numpy as np

from repro.core.retrieval import NEG_INF, unpack_topk
from repro.obs.trace import current_spans

__all__ = ["NEG_INF", "ScorerBackend", "round_trip"]


@runtime_checkable
class ScorerBackend(Protocol):
    """Batched top-K similarity scoring over one immutable table snapshot."""

    name: str  # registry key ("dense" | "ivf" | "pallas")
    table_version: int  # ToolsDatabase version the index was built from
    n_tools: int  # rows in the indexed table
    supports_masks: bool  # can honor [Q, T] candidate masks natively

    def topk(
        self,
        queries: np.ndarray,  # [Q, D] float32 unit rows
        k: int,
        candidate_mask: Optional[np.ndarray] = None,  # [Q, T] {0,1} or None
    ) -> Tuple[np.ndarray, np.ndarray]:
        """(scores [Q, k], indices [Q, k]) by descending similarity."""
        ...


def round_trip(
    fn: Callable[[np.ndarray, Optional[np.ndarray]], jax.Array],
    queries: np.ndarray,
    candidate_mask: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """One device top-K call from the host: upload, dispatch, wait.

    `fn(queries, mask)` is the jitted scorer over the backend's
    device-resident table; it returns one packed `[Q, 2k]` int32 block
    (`pack_topk`), which is split on the host into (scores, indices)
    views (`unpack_topk`), bit for bit the program's. The NumPy inputs go
    straight to `fn`: the jitted call copies them up as it dispatches,
    which on a TPU v5e costs ~0.18 ms a call less than a `jnp.asarray` of
    each before the call (1.15 against 1.33 ms a round trip at Q=32, mean
    of 3,200 calls). Each step is a span of the calling batch:

      index.upload    the query block (and mask) in the dtypes that cross
                      (a view unless a dtype must narrow); no copy;
      index.dispatch  the jitted call, with its copy of the inputs up,
                      until it returns its future;
      index.wait      until the block is on the host: the device's queue
                      and run, then its one copy back.

    Dispatch is asynchronous, so the device's transfer and compute time
    land in `index.wait`. No explicit wait is added to split it further:
    on a TPU v5e a `block_until_ready` before the copy cost ~0.11 ms a
    call, ~6% of the whole round trip. The copies and their bytes (the
    padded query block and mask up, the block down: Q x k x 8 bytes) go
    to the batch's recorder.
    """
    spans = current_spans()
    with spans.span("index.upload"):
        q = _crossing(queries)
        mask = None if candidate_mask is None else _crossing(candidate_mask)
    with spans.span("index.dispatch"):
        block = fn(q, mask)
    with spans.span("index.wait"):
        block = np.asarray(block)
    spans.transfer(h2d=q.nbytes, d2h=block.nbytes)
    if mask is not None:
        spans.transfer(h2d=mask.nbytes)
    return unpack_topk(block, block.shape[1] // 2)


def _crossing(x: np.ndarray) -> np.ndarray:
    """`x` in the dtype the jitted call gives it on the device (int64 and
    float64 narrow to 32 bits unless x64 is enabled)."""
    return np.asarray(x, dtype=jax.dtypes.canonicalize_dtype(x.dtype))
