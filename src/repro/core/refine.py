"""OATS-S1: iterative outcome-guided embedding refinement (Alg. 1, §4.1).

The paper's core contribution. Pure JAX: one jitted function runs all N
passes (outcome collection -> centroid interpolation -> momentum blend),
unrolled at trace time (N is static),
and a separate validation gate (Alg. 1 step 5) accepts the refined table only
if held-out Recall@K improves. Shardable over the tool axis for very large
tool databases (the [T, D] table and all [Q, T] masks are embarrassingly
parallel in T under pjit).

Update rule (Eq. 7), per tool i with |Q_i^+| >= 1:

    e_hat = (1 - alpha) * e + alpha * centroid(Q_i^+) - beta * centroid(Q_i^-)
    e_hat = e_hat / ||e_hat||
    e_new = mu * e_prev + (1 - mu) * e_hat        (momentum, from the second pass on)

Defaults are the paper's: alpha=0.3, beta=0.1, N=3, mu=0.5, K=5.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.outcomes import collect_outcomes
from repro.metrics.retrieval import batched_ndcg_at_k, batched_recall_at_k
from repro.obs.trace import current_spans

__all__ = ["RefineConfig", "RefineResult", "refine_embeddings", "refine_with_gate"]


@dataclasses.dataclass(frozen=True)
class RefineConfig:
    alpha: float = 0.3  # attraction toward positive centroid
    beta: float = 0.1  # repulsion from negative centroid (beta < alpha, §4.1)
    iterations: int = 3  # N
    momentum: float = 0.5  # mu
    k: int = 5  # top-K used both for outcome logs and the validation gate
    positives: str = "ground_truth"  # see outcomes.py
    # validation-gate metric: "recall" (Alg. 1 step 5, the offline default)
    # or "ndcg". With streamed-outcome relevance every logged positive was
    # in the serving top-K by construction, so held-out Recall@K starts at
    # exactly 1.0 and the gate can only tie or reject; rank-sensitive NDCG
    # still registers improvement (positives pulled toward rank 1) — the
    # online control plane gates on it.
    gate_metric: str = "recall"
    # materialize the [N+1, T, D] per-iteration history (Fig. 4 convergence
    # plots). The control plane's repeated refinements on large tables turn
    # this off: the buffer is N+1 full table copies of pure overhead there.
    keep_history: bool = True


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RefineResult:
    embeddings: jnp.ndarray  # [T, D] refined (post-gate) tool table
    accepted: jnp.ndarray  # bool — validation gate decision
    recall_before: jnp.ndarray
    recall_after: jnp.ndarray
    # [N+1, T, D] per-iteration tables (fig. 4 convergence), or None when
    # the run was configured with keep_history=False
    history: Optional[jnp.ndarray]


def _masked_centroid(mask: jnp.ndarray, query_emb: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """mask: [Q, T]; query_emb: [Q, D] -> ([T, D] centroids, [T] counts)."""
    counts = mask.sum(axis=0)  # [T]
    sums = mask.T @ query_emb  # [T, D]
    centroids = sums / jnp.maximum(counts, 1.0)[:, None]
    return centroids, counts


@functools.partial(
    jax.jit,
    static_argnames=(
        "alpha", "beta", "iterations", "momentum", "k", "positives", "keep_history"
    ),
)
def refine_embeddings(
    tool_emb: jnp.ndarray,  # [T, D] original table e(d_i)
    query_emb: jnp.ndarray,  # [Q, D] train-split query embeddings
    relevance: jnp.ndarray,  # [Q, T] binary outcome labels
    candidate_mask: Optional[jnp.ndarray] = None,
    *,
    alpha: float = 0.3,
    beta: float = 0.1,
    iterations: int = 3,
    momentum: float = 0.5,
    k: int = 5,
    positives: str = "ground_truth",
    keep_history: bool = True,
) -> jnp.ndarray:
    """Run Alg. 1 steps 1-4.

    With `keep_history` (default) returns [N+1, T, D]: the table after each
    iteration (index 0 = original), so callers can plot convergence (paper
    Fig. 4). With `keep_history=False` returns only the final [T, D] table —
    the N+1 table copies are never materialized, which is what the online
    control plane wants for repeated refinements on large tool sets.
    """

    def one_pass(e_prev, blend):
        # Steps 1-2: outcome logs against *current* embeddings — each pass
        # exposes the new hard negatives created by the previous update.
        logs = collect_outcomes(
            query_emb, e_prev, relevance, candidate_mask, k=k, positives=positives
        )
        # Step 3: centroid interpolation (Eq. 7)
        pos_c, pos_n = _masked_centroid(logs.pos_mask, query_emb)
        neg_c, neg_n = _masked_centroid(logs.neg_mask, query_emb)
        e_hat = (1.0 - alpha) * e_prev + alpha * pos_c
        e_hat = e_hat - beta * jnp.where((neg_n > 0)[:, None], 1.0, 0.0) * neg_c
        e_hat = e_hat / jnp.maximum(jnp.linalg.norm(e_hat, axis=-1, keepdims=True), 1e-9)
        # tools with no positive outcomes stay at their previous embedding
        e_hat = jnp.where((pos_n > 0)[:, None], e_hat, e_prev)
        if not blend:
            return e_hat
        # Step 4: momentum blend with the previous pass's table
        blended = momentum * e_prev + (1.0 - momentum) * e_hat
        return blended / jnp.maximum(jnp.linalg.norm(blended, axis=-1, keepdims=True), 1e-9)

    # `iterations` is static, so the passes are unrolled: pass 0 has no blend
    # and the history is a stack of the pass tables. Keep the blend choice
    # out of traced code: a `fori_loop` choosing it by a traced `n > 0`
    # blended pass 0 too on TPU v5e.
    tables = [tool_emb]
    for n in range(iterations):
        tables.append(one_pass(tables[-1], blend=n > 0))
    return jnp.stack(tables) if keep_history else tables[-1]


def _gate_metric_at_k(
    query_emb: jnp.ndarray,
    tool_emb: jnp.ndarray,
    relevance: jnp.ndarray,
    candidate_mask: Optional[jnp.ndarray],
    k: int,
    metric: str = "recall",
) -> jnp.ndarray:
    sims = query_emb @ tool_emb.T
    if candidate_mask is not None:
        sims = jnp.where(candidate_mask > 0, sims, -1e30)
    _, topk = jax.lax.top_k(sims, min(k, sims.shape[1]))
    if metric == "ndcg":
        return batched_ndcg_at_k(topk, relevance)
    assert metric == "recall", f"unknown gate metric {metric!r}"
    return batched_recall_at_k(topk, relevance)


def refine_with_gate(
    tool_emb: jnp.ndarray,
    train_query_emb: jnp.ndarray,
    train_relevance: jnp.ndarray,
    val_query_emb: jnp.ndarray,
    val_relevance: jnp.ndarray,
    config: RefineConfig = RefineConfig(),
    train_candidate_mask: Optional[jnp.ndarray] = None,
    val_candidate_mask: Optional[jnp.ndarray] = None,
) -> RefineResult:
    """Alg. 1 incl. step 5: accept the refined table only if the held-out
    gate metric (Recall@K by default, NDCG@K via `config.gate_metric`) does
    not degrade.

    The gate guarantees the deployed system cannot degrade below the static
    baseline (§4.1) — this invariant is property-tested.
    `RefineResult.recall_before/after` hold whichever gate metric ran.

    The passes and the gate are timed as spans ``fit.refine`` and
    ``fit.gate`` of the calling thread's `current_spans()` (nothing is
    recorded outside a bound recorder); each ends when its result is ready.
    """
    spans = current_spans()
    with spans.span("fit.refine"):
        out = refine_embeddings(
            tool_emb,
            train_query_emb,
            train_relevance,
            train_candidate_mask,
            alpha=config.alpha,
            beta=config.beta,
            iterations=config.iterations,
            momentum=config.momentum,
            k=config.k,
            positives=config.positives,
            keep_history=config.keep_history,
        )
        jax.block_until_ready(out)
    history = out if config.keep_history else None
    refined = out[-1] if config.keep_history else out
    with spans.span("fit.gate"):
        r_before = _gate_metric_at_k(
            val_query_emb, tool_emb, val_relevance, val_candidate_mask,
            config.k, config.gate_metric,
        )
        r_after = _gate_metric_at_k(
            val_query_emb, refined, val_relevance, val_candidate_mask,
            config.k, config.gate_metric,
        )
        accepted = r_after >= r_before
        final = jax.block_until_ready(jnp.where(accepted, refined, tool_emb))
    return RefineResult(
        embeddings=final,
        accepted=accepted,
        recall_before=r_before,
        recall_after=r_after,
        history=history,
    )
