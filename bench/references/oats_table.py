"""Plain reference of the deployed tool table: OATS-S1 refinement, then tiling.

It imports nothing of the program under test. From the configuration's
benchmark data (word vectors, tool descriptions, queries, relevance labels,
candidate pools, the train split) and the run's seed it rebuilds, in
float64 NumPy, the table the configuration states:

  base     each tool's description embedded: the mean of its word vectors,
           L2-normalized (`exact_topk.embed`);
  split    the train queries split 85/15 into fit and gate-validation
           queries by a permutation from `default_rng(split_seed)`;
  refine   OATS-S1 (arXiv:2603.13426, Alg. 1, Eq. 7), `iterations` passes:
           each pass retrieves every fit query's top-K within its candidate
           pool under the current table; a tool's positives are its labelled
           fit queries, its negatives the retrieved ones it is not labelled
           for; a tool with a positive moves to
             normalize((1 - alpha) e + alpha centroid(pos) - beta centroid(neg))
           (the last term only where it has a negative), and from the second
           pass on the result is blended, normalize(mu e + (1 - mu) e_hat);
  gate     the refined table is deployed only if mean Recall@K of the
           validation queries within their pools does not fall;
  tile     a registry of `n_tools` rows beyond the benchmark's: row i is row
           i mod T plus `noise` x standard normal draws from
           `default_rng(seed)` (one [n_tools - T, D] draw, row-major),
           re-normalized; the first T rows are the table itself.

`table_off_pct` holds a deployed table to it: the share of rows (%) whose
largest element gap exceeds `ROW_TOL`. float32 rounding of the refinement
moves a row by about 1e-7; a row the refinement moved differently (another
outcome mask, another gate decision, other noise) moves by 1e-3 or more.
Where the gate's two recalls tie to within rounding, either decision is the
configuration's, and the table is held to the nearer of the two.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from bench.references.exact_topk import embed

ROW_TOL = 1e-4
GATE_TIE = 1e-6
MASKED = -1e30  # score of a tool outside a query's candidate pool

# every matrix product of the fit, `a @ b.T`; a control passes a rounded one
Product = Callable[[np.ndarray, np.ndarray], np.ndarray]


def _product(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return a @ b.T


def _top_k(sims: np.ndarray, k: int) -> np.ndarray:
    """Indices of the k highest scores a row, the lower index first on ties."""
    return np.argsort(-sims, axis=1, kind="stable")[:, :k]


def _pooled(sims: np.ndarray, pools: Optional[np.ndarray]) -> np.ndarray:
    return sims if pools is None else np.where(pools > 0, sims, MASKED)


def _centroids(mask: np.ndarray, q: np.ndarray,
               product: Product) -> Tuple[np.ndarray, np.ndarray]:
    counts = mask.sum(axis=0)
    return product(mask.T, q.T) / np.maximum(counts, 1.0)[:, None], counts


def _unit(x: np.ndarray) -> np.ndarray:
    return x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-9)


def refine(table: np.ndarray, q: np.ndarray, rel: np.ndarray, pools: Optional[np.ndarray],
           alpha: float, beta: float, iterations: int, momentum: float, k: int,
           product: Product = _product) -> np.ndarray:
    """OATS-S1's passes over the fit queries (see the module docstring)."""
    e = table
    k = min(k, table.shape[0])
    for n in range(iterations):
        top = _top_k(_pooled(product(q, e), pools), k)
        retrieved = np.zeros_like(rel)
        retrieved[np.arange(len(q))[:, None], top] = 1.0
        pos_c, pos_n = _centroids(rel, q, product)
        neg_c, neg_n = _centroids(retrieved * (1.0 - rel), q, product)
        e_hat = (1.0 - alpha) * e + alpha * pos_c - beta * (neg_n > 0)[:, None] * neg_c
        e_hat = np.where((pos_n > 0)[:, None], _unit(e_hat), e)
        e = _unit(momentum * e + (1.0 - momentum) * e_hat) if n > 0 else e_hat
    return e


def recall_at_k(q: np.ndarray, table: np.ndarray, rel: np.ndarray, pools: Optional[np.ndarray],
                k: int, product: Product = _product) -> float:
    """Mean Recall@K over the queries with a relevant tool."""
    top = _top_k(_pooled(product(q, table), pools), min(k, table.shape[0]))
    hits = np.take_along_axis(rel, top, axis=1).sum(axis=1)
    n_rel = rel.sum(axis=1)
    valid = n_rel > 0
    return float((hits[valid] / n_rel[valid]).sum() / max(int(valid.sum()), 1))


def tile(table: np.ndarray, n_tools: int, seed: int, noise: float) -> np.ndarray:
    """The registry of `n_tools` rows grown from `table` (see the module docstring)."""
    t, d = table.shape
    if n_tools <= t:
        return table
    big = table[np.arange(n_tools) % t]
    draws = np.random.default_rng(seed).standard_normal(size=(n_tools - t, d))
    big[t:] = _unit(big[t:] + noise * draws)
    return big


def build(word_vecs: np.ndarray, desc_tokens: Sequence[np.ndarray],
          query_tokens: Sequence[np.ndarray], relevant: Sequence[np.ndarray],
          candidates: Optional[Sequence[np.ndarray]], train_idx: np.ndarray,
          n_tools: int, seed: int, spec: dict, k: int,
          product: Product = _product) -> List[np.ndarray]:
    """The configuration's table, float64; two tables where the gate ties."""
    n_t, n_q = len(desc_tokens), len(query_tokens)
    base = embed(word_vecs, desc_tokens)
    q_all = embed(word_vecs, query_tokens)
    rel = np.zeros((n_q, n_t))
    for j, r in enumerate(relevant):
        rel[j, r] = 1.0
    pools = None
    if candidates is not None:
        pools = np.zeros((n_q, n_t))
        for j, c in enumerate(candidates):
            pools[j, c] = 1.0
    perm = np.random.default_rng(spec["split_seed"]).permutation(len(train_idx))
    n_val = max(int(round(spec["gate_val_frac"] * len(train_idx))), 1)
    fit, val = train_idx[np.sort(perm[n_val:])], train_idx[np.sort(perm[:n_val])]

    def sub(m, idx):
        return None if m is None else m[idx]

    refined = refine(base, q_all[fit], rel[fit], sub(pools, fit), spec["alpha"], spec["beta"],
                     spec["iterations"], spec["momentum"], k, product)
    before = recall_at_k(q_all[val], base, rel[val], sub(pools, val), k, product)
    after = recall_at_k(q_all[val], refined, rel[val], sub(pools, val), k, product)
    if abs(after - before) <= GATE_TIE:
        chosen = [refined, base]
    else:
        chosen = [refined] if after > before else [base]
    return [tile(c, n_tools, seed, spec["registry_noise"]) for c in chosen]


def rows_off(table: np.ndarray, reference: np.ndarray) -> int:
    """Rows whose largest element gap to the reference exceeds ROW_TOL."""
    if table.shape != reference.shape:
        return table.shape[0]
    off = 0
    for lo in range(0, table.shape[0], 1 << 16):
        gap = np.abs(table[lo : lo + (1 << 16)].astype(np.float64) - reference[lo : lo + (1 << 16)])
        off += int((gap.max(axis=1) > ROW_TOL).sum())
    return off


def table_off_pct(table: np.ndarray, references: Sequence[np.ndarray]) -> float:
    """Share of the table's rows (%) off the nearest of the reference's tables."""
    return 100.0 * min(rows_off(table, r) for r in references) / max(table.shape[0], 1)
