"""IVFBackend: coarse k-means quantization + int8 candidate scoring + exact
re-rank — sublinear per-query work for MCP-registry-scale tool tables.

Why: brute force is O(T·D) per query; at 100k tools that is ~40M MACs/query
and the 10 ms CPU budget starts to bind. IVF makes per-query work
O(C·D + nprobe·(T/C)·D): score C coarse centroids, visit only the `nprobe`
closest clusters, shortlist their members with int8 codes, and exact-re-rank
the shortlist in fp32. With the default C ≈ 4·√T and nprobe=8, a 100k-tool
query touches ~650 candidate rows instead of 100k.

Build (all deterministic in `config.seed`):

  * spherical k-means over the (unit-row) table — trained on a bounded
    sample (`train_sample`, FAISS-style) then one full assignment pass, so
    build cost stays O(T·C·D) not O(iters·T·C·D);
  * members stored CSR-style in cluster order (`member_ids` + `offsets`),
    so probing a cluster is a contiguous slice;
  * member embeddings stored as int8 codes with per-dimension scales,
    produced by `models/quant.quantize_tree` — the same symmetric
    per-channel machinery the serving pools use for weights. Candidate
    scoring never dequantizes: `score ≈ (q ⊙ scale) · codes^T`;
  * the fp32 snapshot is retained for the exact re-rank, so the scores a
    query returns are true similarities of the indexed table (the contract
    `RouteResult.scores` depends on).

Query: each query probes its `nprobe` coarse-closest clusters (expanded in
coarse order for the rare query whose probed clusters hold fewer than the
`rerank_multiplier · k` shortlist quota — tiny/skewed tables). Scoring is
*cluster-major*, not query-major: the batch's (query, cluster) pairs are
grouped by cluster, and each probed cluster is scored ONCE for all queries
probing it — one contiguous int8 slice (no index gather), one dtype
conversion, one [n_q_probing, cluster_size] GEMM. At batch 64 / 100k tools
this is ~4x faster than a per-query loop: the python overhead amortizes
over clusters instead of (query × cluster) pairs and the GEMMs are big
enough for BLAS. The shortlist is then re-ranked exactly per query and the
top-k emitted; rows with fewer than k reachable candidates pad `NEG_INF`.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import numpy as np

from repro.core.retrieval import NEG_INF
from repro.models.quant import quantize_tree
from repro.obs.trace import current_spans

__all__ = ["IVFConfig", "IVFBackend"]


@dataclasses.dataclass(frozen=True)
class IVFConfig:
    n_clusters: Optional[int] = None  # default: ~4·√T, clamped to [8, T//4]
    nprobe: int = 8  # clusters visited per query (floor; see shortlist quota)
    kmeans_iters: int = 6
    train_sample: int = 20_000  # k-means training subsample bound
    rerank_multiplier: int = 8  # exact-re-rank shortlist = multiplier · k
    seed: int = 0


def _unit_rows(x: np.ndarray) -> np.ndarray:
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def _chunked_argmax_sim(x: np.ndarray, centroids: np.ndarray, chunk: int = 8192) -> np.ndarray:
    """argmax_c <x_i, centroid_c> without materializing the full [N, C] block."""
    out = np.empty(x.shape[0], dtype=np.int32)
    for lo in range(0, x.shape[0], chunk):
        out[lo : lo + chunk] = np.argmax(x[lo : lo + chunk] @ centroids.T, axis=1)
    return out


class IVFBackend:
    name = "ivf"
    supports_masks = False

    def __init__(
        self,
        table: np.ndarray,
        table_version: int,
        config: IVFConfig = IVFConfig(),
        warm_start: Optional[np.ndarray] = None,
    ):
        """`warm_start`: centroids from a previous index over an earlier
        version of this table (`warm_start_state()`), used to seed k-means
        instead of random rows. Control-plane swaps move the table gently
        (centroid refinement preserves most geometry), so warm-started
        k-means converges in a fraction of the iterations — the manager
        passes it automatically on swap-triggered rebuilds. A shape-
        incompatible warm start (different cluster count/dim) is ignored."""
        table = np.asarray(table, np.float32)
        self.table_version = int(table_version)
        self.config = config
        self.n_tools, d = table.shape
        self._table = table  # fp32, for the exact re-rank
        rng = np.random.default_rng(config.seed)

        n_clusters = config.n_clusters or int(round(4 * math.sqrt(self.n_tools)))
        n_clusters = max(1, min(n_clusters, max(self.n_tools // 4, 1)))
        self.n_clusters = n_clusters

        # ---- spherical k-means (sampled train, full final assign) ---------
        if self.n_tools > config.train_sample:
            train = table[rng.choice(self.n_tools, config.train_sample, replace=False)]
        else:
            train = table
        if warm_start is not None and np.shape(warm_start) == (n_clusters, d):
            centroids = _unit_rows(np.asarray(warm_start, np.float32).copy())
        else:
            centroids = train[rng.choice(len(train), n_clusters, replace=False)].copy()
        prev_assign: Optional[np.ndarray] = None
        iters_run = 0
        for _ in range(config.kmeans_iters):
            assign = _chunked_argmax_sim(train, centroids)
            if prev_assign is not None and np.array_equal(assign, prev_assign):
                # converged: re-updating from an identical assignment is the
                # identity, so the remaining iterations are pure waste —
                # this is what makes a warm start cheap, not just safe
                break
            prev_assign = assign
            iters_run += 1
            sums = np.zeros_like(centroids)
            np.add.at(sums, assign, train)
            counts = np.bincount(assign, minlength=n_clusters)
            empty = counts == 0
            centroids = _unit_rows(sums / np.maximum(counts, 1)[:, None])
            if empty.any():  # re-seed dead centroids from random train rows
                centroids[empty] = train[rng.choice(len(train), int(empty.sum()))]
        self.kmeans_iters_run = iters_run
        self.centroids = centroids.astype(np.float32)

        # ---- inverted lists: CSR layout in cluster order ------------------
        assign = _chunked_argmax_sim(table, self.centroids)
        order = np.argsort(assign, kind="stable")
        self.member_ids = order.astype(np.int64)
        self.offsets = np.searchsorted(assign[order], np.arange(n_clusters + 1))

        # ---- int8 cluster storage (models/quant machinery) ----------------
        leaf = quantize_tree({"codes": table[order]})["codes"]
        if isinstance(leaf, dict):  # {"q": int8 [T, D], "scale": bf16 [1, D]}
            self._codes = np.asarray(leaf["q"])
            self._scale = np.asarray(leaf["scale"]).astype(np.float32).reshape(-1)
        else:  # tiny tables fall below quant's size floor; store fp32 codes
            self._codes = np.asarray(leaf, np.float32)
            self._scale = np.ones(d, np.float32)
        # query-time scratch: slice views instead of per-cluster aranges; the
        # conversion buffer is sized here but allocated per call (topk must
        # stay re-entrant — routers share backends across serving threads)
        self._pos = np.arange(self.n_tools, dtype=np.int64)
        self._max_cluster = int((self.offsets[1:] - self.offsets[:-1]).max(initial=1))
        self._dim = d

    def warm_start_state(self) -> np.ndarray:
        """Centroids to seed the next rebuild's k-means (see `warm_start`).

        `ToolIndexManager` calls this on the outgoing backend when a
        swap/rollback triggers a rebuild, cutting the dominant k-means cost
        of the 10-14 s build at registry scale."""
        return self.centroids

    # ------------------------------------------------------------------ query
    def topk(
        self,
        queries: np.ndarray,
        k: int,
        candidate_mask: Optional[np.ndarray] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        assert candidate_mask is None, (
            "IVFBackend cannot honor candidate masks (tools outside the probed "
            "clusters would silently vanish); ToolIndexManager routes masked "
            "batches to the exact fallback"
        )
        # host NumPy end to end: one span, no device round trip to split
        with current_spans().span("index.ivf"):
            return self._topk(queries, k)

    def _topk(self, queries: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
        q = np.asarray(queries, np.float32)
        n_q = q.shape[0]
        if n_q == 0:  # contract: any Q, including an empty batch
            return (
                np.full((0, k), NEG_INF, np.float32),
                np.zeros((0, k), np.int64),
            )
        cfg = self.config
        shortlist = max(cfg.rerank_multiplier * k, k)
        nprobe = min(cfg.nprobe, self.n_clusters)
        sizes = self.offsets[1:] - self.offsets[:-1]  # [C]

        # ---- probe selection: top-nprobe clusters per query ---------------
        qc = q @ self.centroids.T  # [Q, C]
        if nprobe < self.n_clusters:
            probes = np.argpartition(-qc, nprobe - 1, axis=1)[:, :nprobe]
        else:
            probes = np.broadcast_to(
                np.arange(self.n_clusters), (n_q, self.n_clusters)
            )
        under = np.flatnonzero(sizes[probes].sum(axis=1) < min(shortlist, self.n_tools))
        if len(under):
            probe_list = list(probes)
            quota = min(shortlist, self.n_tools)
            for j in under:
                # rare: probed clusters too small for the shortlist quota —
                # extend this query's probes in coarse order until it is met
                ranked = np.argsort(-qc[j], kind="stable")
                n_cand = np.cumsum(sizes[ranked])
                stop = int(np.searchsorted(n_cand, quota)) + 1
                probe_list[j] = ranked[: max(stop, nprobe)]
            pair_q = np.concatenate(
                [np.full(len(p), j, np.int64) for j, p in enumerate(probe_list)]
            )
            pair_c = np.concatenate(probe_list)
        else:
            pair_q = np.repeat(np.arange(n_q, dtype=np.int64), nprobe)
            pair_c = probes.ravel()

        # ---- cluster-major int8 scoring -----------------------------------
        # group the (query, cluster) pairs by cluster: each probed cluster is
        # scored once for ALL queries probing it — a contiguous codes slice
        # (no gather) and one GEMM per cluster instead of per pair
        order = np.argsort(pair_c, kind="stable")
        pair_q, pair_c = pair_q[order], pair_c[order]
        bounds = np.flatnonzero(np.diff(pair_c)) + 1
        starts = np.concatenate(([0], bounds))
        ends = np.concatenate((bounds, [len(pair_c)]))
        qs = q * self._scale  # fold the int8 scales into the queries once
        cand_scores: list = [[] for _ in range(n_q)]
        cand_pos: list = [[] for _ in range(n_q)]
        # one conversion buffer per CALL (not per cluster: allocation cost;
        # not per backend: concurrent topk calls would corrupt each other)
        convert_buf = np.empty((self._max_cluster, self._dim), np.float32)
        for a, b in zip(starts, ends):
            c = pair_c[a]
            lo, hi = self.offsets[c], self.offsets[c + 1]
            if hi == lo:
                continue
            block = convert_buf[: hi - lo]
            np.copyto(block, self._codes[lo:hi], casting="unsafe")
            scores = qs[pair_q[a:b]] @ block.T  # [n_q_probing, cluster_size]
            pos = self._pos[lo:hi]  # view, no arange
            for i, j in enumerate(pair_q[a:b]):
                cand_scores[j].append(scores[i])
                cand_pos[j].append(pos)

        # ---- per-query shortlist + exact fp32 re-rank ---------------------
        out_s = np.full((n_q, k), NEG_INF, np.float32)
        out_i = np.zeros((n_q, k), np.int64)
        for j in range(n_q):
            if not cand_pos[j]:
                continue
            approx = np.concatenate(cand_scores[j])
            pos = np.concatenate(cand_pos[j])
            if len(pos) > shortlist:
                sel = np.argpartition(-approx, shortlist)[:shortlist]
                pos = pos[sel]
            ids = self.member_ids[pos]
            exact = self._table[ids] @ q[j]
            kk = min(k, len(ids))
            if len(ids) > kk:
                top = np.argpartition(-exact, kk - 1)[:kk]
            else:
                top = np.arange(len(ids))
            top = top[np.argsort(-exact[top], kind="stable")]
            out_i[j, :kk] = ids[top]
            out_s[j, :kk] = exact[top]
        return out_s, out_i
