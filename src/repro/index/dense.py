"""DenseBackend: exact brute-force scoring — the default and the oracle.

This is the gateway's first serving path: one jitted `topk_dense` call
(matmul + `lax.top_k`, optional per-query candidate masks, the result packed
into one block) against a device-resident copy of the table snapshot. It exists as a
backend so the gateway stops hardcoding it: the numerics are unchanged, only
the ownership of the device copy moved from `SemanticRouter._device_table`
into the index layer.

Per-query cost is O(T·D) — at MCP-registry scale (100k tools) that is the
brute-force wall `IVFBackend` exists to avoid; dense remains the fallback
every other backend is validated against (and the path the manager serves
while an index rebuild is in flight).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.retrieval import topk_dense
from repro.index.base import round_trip

__all__ = ["DenseBackend"]


class DenseBackend:
    name = "dense"
    supports_masks = True
    # build == one device upload: the manager rebuilds inline on swap rather
    # than paying a thread spawn + duplicate fallback upload per version
    build_is_cheap = True

    def __init__(self, table: np.ndarray, table_version: int):
        table = np.asarray(table, np.float32)
        self.table_version = int(table_version)
        self.n_tools = table.shape[0]
        self._table_j = jnp.asarray(table)  # device-resident, built once
        self.upload_bytes = self._table_j.nbytes

    def topk(
        self,
        queries: np.ndarray,
        k: int,
        candidate_mask: Optional[np.ndarray] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        return round_trip(
            lambda q, mask: topk_dense(q, self._table_j, k, mask),
            queries, candidate_mask,
        )
