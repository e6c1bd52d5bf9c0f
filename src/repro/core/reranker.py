"""OATS-S2: learned re-ranking MLP (§4.2). 2,625 parameters, [7, 64, 32, 1].

Trained with BCE (Eq. 9) over outcome-labelled (query, candidate) pairs.
At inference: retrieve C = alpha*K candidates by similarity (alpha=5), rescore
with f_phi, return the top-K by MLP score. The paper's headline negative
result — the re-ranker *hurts* below a ~10:1 data-to-tool ratio — reproduces
on the toolbench-like benchmark (<0.15 positives/tool).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.core.features import N_FEATURES

__all__ = [
    "RerankerConfig",
    "init_mlp",
    "mlp_forward",
    "train_reranker",
    "mlp_param_count",
    "rerank_topk",
    "rerank_topk_scored",
]

LAYERS = (N_FEATURES, 64, 32, 1)  # paper §4.2: [7, 64, 32, 1] => 2,625 params


@dataclasses.dataclass(frozen=True)
class RerankerConfig:
    lr: float = 1e-3
    epochs: int = 30
    batch_size: int = 512
    dropout: float = 0.1  # §5.5
    weight_decay: float = 1e-4
    seed: int = 0
    candidate_multiplier: int = 5  # alpha: retrieve C = alpha*K then re-rank


def init_mlp(key: jax.Array) -> dict:
    params = {}
    for li, (din, dout) in enumerate(zip(LAYERS[:-1], LAYERS[1:])):
        key, wk = jax.random.split(key)
        params[f"w{li}"] = jax.random.normal(wk, (din, dout), jnp.float32) * jnp.sqrt(
            2.0 / din
        )
        params[f"b{li}"] = jnp.zeros((dout,), jnp.float32)
    return params


def mlp_param_count(params: dict) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


def mlp_forward(
    params: dict, x: jnp.ndarray, *, dropout: float = 0.0, key: jax.Array | None = None
) -> jnp.ndarray:
    """x: [..., 7] -> logits [...]. Sigmoid is applied in the loss/score."""
    h = x
    n_layers = len(LAYERS) - 1
    for li in range(n_layers):
        h = h @ params[f"w{li}"] + params[f"b{li}"]
        if li < n_layers - 1:
            h = jax.nn.relu(h)
            if dropout > 0.0 and key is not None:
                key, dk = jax.random.split(key)
                keep = jax.random.bernoulli(dk, 1.0 - dropout, h.shape)
                h = jnp.where(keep, h / (1.0 - dropout), 0.0)
    return h[..., 0]


def _bce_loss(params, x, y, key, dropout):
    logits = mlp_forward(params, x, dropout=dropout, key=key)
    # Eq. 9: binary cross-entropy on outcome labels
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * y + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


def train_reranker(
    features: np.ndarray,  # [N, 7] flattened (query, candidate) rows
    labels: np.ndarray,  # [N] outcome o in {0,1}
    config: RerankerConfig = RerankerConfig(),
) -> tuple[dict, list[float]]:
    """BCE training with AdamW. Returns (params, per-epoch losses)."""
    key = jax.random.PRNGKey(config.seed)
    key, ik = jax.random.split(key)
    params = init_mlp(ik)
    opt = optim.adamw(config.lr, weight_decay=config.weight_decay)
    opt_state = opt.init(params)

    x = jnp.asarray(features, jnp.float32)
    y = jnp.asarray(labels, jnp.float32)
    n = x.shape[0]
    bs = min(config.batch_size, n)
    steps_per_epoch = max(n // bs, 1)

    @jax.jit
    def step(params, opt_state, xb, yb, key):
        loss, grads = jax.value_and_grad(_bce_loss)(params, xb, yb, key, config.dropout)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optim.apply_updates(params, updates), opt_state, loss

    losses = []
    for epoch in range(config.epochs):
        key, pk = jax.random.split(key)
        perm = jax.random.permutation(pk, n)
        epoch_loss = 0.0
        for s in range(steps_per_epoch):
            idx = jax.lax.dynamic_slice_in_dim(perm, s * bs, bs)
            key, dk = jax.random.split(key)
            params, opt_state, loss = step(params, opt_state, x[idx], y[idx], dk)
            epoch_loss += float(loss)
        losses.append(epoch_loss / steps_per_epoch)
    return params, losses


@functools.partial(jax.jit, static_argnames=("k",))
def rerank_topk_scored(
    params: dict,
    features: jnp.ndarray,  # [Q, C, 7] similarity-ordered candidates
    cand_idx: jnp.ndarray,  # [Q, C]
    k: int,
    valid: jnp.ndarray | None = None,  # [Q, C] — False for padded slots
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Re-score candidates with f_phi; return (top-K ids, their f_phi scores).

    The returned scores are the MLP logits that *produced* the ordering, so
    serving code can report the ranking signal actually used (not the
    pre-rerank similarities, which may order differently).
    """
    with jax.named_scope("rerank"):
        scores = mlp_forward(params, features)  # [Q, C]
        if valid is not None:
            scores = jnp.where(valid, scores, -1e30)
        top_scores, order = jax.lax.top_k(scores, k)
        return jnp.take_along_axis(cand_idx, order, axis=1), top_scores


def rerank_topk(
    params: dict,
    features: jnp.ndarray,
    cand_idx: jnp.ndarray,
    k: int,
    valid: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Ids-only wrapper around `rerank_topk_scored`."""
    return rerank_topk_scored(params, features, cand_idx, k, valid)[0]
