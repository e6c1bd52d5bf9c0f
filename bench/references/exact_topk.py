"""Plain reference of a route without learned stages: bag-of-words embedding,
exact similarity against the table, top-K.

It imports nothing of the program under test. It takes the deployment's
data (the vocabulary's word vectors and the tool table of the version a
result reports, as a database reference takes its rows) and recomputes
everything a route computes from them, in float64 NumPy:

  embedding  mean of the query tokens' word vectors, L2-normalized;
  scores     embedding . table row, for every tool;
  top-K      the K highest scores.

`compare` holds served results to it and returns the numbers that decide
`correct`:

  topk_err    the widest score by which a served route departs from the
              exact top-K: the larger of its two parts,
  score_err   widest |served score - reference score of the served tool|, and
  rank_gap    widest (best reference score among tools not served) minus
              (worst reference score among tools served), or 0 where every
              served set is the exact top-K; positive when a better tool was
              left out, even if every served score is that tool's own;
  malformed   results that are not K distinct in-range tools with finite,
              non-increasing scores, or that report another table version.

Scoring every tool in float64 for every checked route would take longer
than a run's window at 10^6 tools, so tools are screened in float32 first:
only a tool whose float32 score lies within `SCREEN_TOL` of the worst
served reference score can beat it, and only such tools (at most K +
`SCREEN` of them per query, the best by float32) are rescored in float64.
float32 misses a 384-long unit dot product by about 1e-6, far inside the
screen, so `rank_gap` is exact wherever it exceeds -`SCREEN_TOL`.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Sequence

import numpy as np

SCREEN = 16  # tools kept per query beyond K by the float32 screen
SCREEN_TOL = 1e-4  # far above float32 rounding of a 384-long unit dot product (~1e-6)
SCREEN_CELLS = 1 << 24  # float32 scores held at once by the screen (64 MiB)
TOOL_BLOCK = 1 << 16


def embed(word_vecs: np.ndarray, tokens: Sequence[np.ndarray]) -> np.ndarray:
    """[Q, D] float64 unit rows: the mean of each query's word vectors."""
    out = np.zeros((len(tokens), word_vecs.shape[1]))
    lens = np.array([len(t) for t in tokens])
    width = max(1, int(lens.max(initial=1)))
    ids = np.zeros((len(tokens), width), np.int64)
    for i, t in enumerate(tokens):
        ids[i, : len(t)] = t
    inside = np.arange(width)[None, :] < lens[:, None]
    for lo in range(0, len(tokens), 4096):
        vecs = word_vecs[ids[lo : lo + 4096]].astype(np.float64)  # [B, L, D]
        total = np.einsum("bld,bl->bd", vecs, inside[lo : lo + 4096].astype(np.float64))
        mean = total / np.maximum(lens[lo : lo + 4096], 1)[:, None]
        out[lo : lo + 4096] = mean / np.maximum(np.linalg.norm(mean, axis=1, keepdims=True), 1e-9)
    out[lens == 0] = 0.0
    return out


@dataclasses.dataclass
class Route:
    tokens: np.ndarray
    tools: List[int]
    scores: List[float]
    table_version: int


def well_formed(r: Route, k: int, n_tools: int, version: int) -> bool:
    """K distinct in-range tools, finite non-increasing scores, the right version."""
    t, s = r.tools, r.scores
    return (
        len(t) == k and len(s) == k and r.table_version == version and len(set(t)) == k
        and all(0 <= x < n_tools for x in t) and all(math.isfinite(x) for x in s)
        and all(a >= b for a, b in zip(s, s[1:]))
    )


def _rows_dot(e: np.ndarray, table: np.ndarray, rows: np.ndarray, tools: np.ndarray) -> np.ndarray:
    """float64 e[rows[i]] . table[tools[i]] for each i, in blocks."""
    out = np.empty(len(rows))
    for lo in range(0, len(rows), 1 << 15):
        r, t = rows[lo : lo + (1 << 15)], tools[lo : lo + (1 << 15)]
        out[lo : lo + len(r)] = np.einsum("nd,nd->n", e[r], table[t].astype(np.float64))
    return out


def _screen(e: np.ndarray, table: np.ndarray, floor: np.ndarray, cap: int):
    """Per query, up to `cap` tools whose float32 score exceeds floor - SCREEN_TOL."""
    n_q, n_tools = len(e), table.shape[0]
    ids = np.full((n_q, cap), -1, np.int64)
    s32 = np.full((n_q, cap), -np.inf, np.float32)
    e32 = e.astype(np.float32)
    tools_per = min(n_tools, TOOL_BLOCK)
    rows_per = max(1, SCREEN_CELLS // tools_per)
    for t0 in range(0, n_tools, tools_per):
        tab = table[t0 : t0 + tools_per]
        for u0 in range(0, n_q, rows_per):
            s = e32[u0 : u0 + rows_per] @ tab.T
            hit = s > (floor[u0 : u0 + rows_per] - SCREEN_TOL)[:, None]
            for r in np.nonzero(hit.any(axis=1))[0]:
                c = np.nonzero(hit[r])[0]
                mi = np.concatenate([ids[u0 + r], c + t0])
                ms = np.concatenate([s32[u0 + r], s[r, c]])
                keep = np.argsort(-ms, kind="stable")[:cap]
                ids[u0 + r], s32[u0 + r] = mi[keep], ms[keep]
    return ids


def compare(
    word_vecs: np.ndarray, table: np.ndarray, version: int, routes: Sequence[Route], k: int
) -> Dict[str, float]:
    """Hold served routes to the reference; see the module docstring."""
    n_tools = table.shape[0]
    keep = [r for r in routes if well_formed(r, k, n_tools, version)]
    out = {"topk_err": 0.0, "score_err": 0.0, "rank_gap": 0.0,
           "malformed": len(routes) - len(keep), "compared": len(keep)}
    if not keep:
        return out
    # one reference pass per distinct query, and per distinct served answer:
    # hot intents repeat under Zipf traffic
    queries: Dict[bytes, int] = {}
    answers: Dict[tuple, int] = {}
    first, slot, tools, scores = [], [], [], []
    for r in keep:
        qkey = np.asarray(r.tokens, np.int64).tobytes()
        if qkey not in queries:
            queries[qkey] = len(first)
            first.append(r.tokens)
        akey = (queries[qkey], tuple(r.tools), tuple(r.scores))
        if akey not in answers:
            answers[akey] = len(slot)
            slot.append(queries[qkey])
            tools.append(r.tools)
            scores.append(r.scores)
    slot = np.asarray(slot)
    served = np.asarray(tools, np.int64)  # [A, K]
    e = embed(word_vecs, first)  # [U, D] float64
    ref = _rows_dot(e, table, np.repeat(slot, k), served.ravel()).reshape(-1, k)  # [A, K]
    out["score_err"] = float(np.abs(np.asarray(scores, np.float64) - ref).max())
    worst = ref.min(axis=1)
    floor = np.full(len(first), np.inf)
    np.minimum.at(floor, slot, worst)
    cand = _screen(e, table, floor, k + SCREEN)  # [U, cap], -1 where empty
    valid = cand >= 0
    cand64 = np.full(cand.shape, -np.inf)
    rows, cols = np.nonzero(valid)
    cand64[rows, cols] = _rows_dot(e, table, rows, cand[rows, cols])
    for lo in range(0, len(slot), 4096):
        u, ids = slot[lo : lo + 4096], served[lo : lo + 4096]
        taken = (cand[u][:, :, None] == ids[:, None, :]).any(axis=2)
        best = np.where(taken, -np.inf, cand64[u]).max(axis=1)
        out["rank_gap"] = max(out["rank_gap"], float(np.max(best - worst[lo : lo + 4096])))
    out["topk_err"] = max(out["score_err"], out["rank_gap"])
    return out
