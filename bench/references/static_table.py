"""Plain reference of a static-embedding table: no fit, then tiling.

It imports nothing of the program under test. The table is each tool's
description embedded (`exact_topk.embed`: the mean of its word vectors,
L2-normalized) in float64, the static-embedding baseline (SE) that the OATS
paper (arXiv:2603.13426) refines; a registry of `n_tools` rows beyond the
benchmark's tools is grown as `oats_table.tile` grows it, with the
configuration's `table.registry_noise`. `table_off_pct` is
`oats_table.table_off_pct`: the share of rows (%) whose largest element gap
exceeds `oats_table.ROW_TOL`.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from bench.references.exact_topk import embed
from bench.references.oats_table import table_off_pct, tile

__all__ = ["build", "table_off_pct"]


def build(word_vecs: np.ndarray, desc_tokens: Sequence[np.ndarray],
          query_tokens: Sequence[np.ndarray], relevant: Sequence[np.ndarray],
          candidates: Optional[Sequence[np.ndarray]], train_idx: np.ndarray,
          n_tools: int, seed: int, spec: dict, k: int) -> List[np.ndarray]:
    """The configuration's table, float64. The labels, pools and split are
    what a fitted table is built from; a static one reads none of them."""
    base = embed(word_vecs, desc_tokens)
    if n_tools <= len(base):
        return [base]
    return [tile(base, n_tools, seed, spec["registry_noise"])]
