"""Serving-path retrieval: embed query -> similarity -> top-K (Eq. 2).

The hot loop the paper constrains to single-digit milliseconds. Two
implementations share one interface:

  * `topk_dense` / `rank_dense` — fp32 jnp matmul + `lax.top_k`, exact on
    every platform (the dense backend, the exact fallback, and the oracle
    for the Pallas kernel);
  * `repro.kernels.topk_sim.ops.topk_sim` — the TPU-native fused
    similarity+top-K Pallas kernel for pod-co-located routers (DESIGN.md §4).

Candidate masking supports MetaTool-style per-query candidate subsets.

The served programs (`topk_dense`, the Pallas backend's `topk_sim_packed`)
return their top-K as one packed block (`pack_topk`), so a call costs one
device-to-host copy; `unpack_topk` splits it on the host without copying.
`topk_sim` itself keeps its (scores, indices) pair.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["similarities", "rank_dense", "topk_dense", "pack_topk", "unpack_topk"]

NEG_INF = -1e30


def similarities(query_emb: jnp.ndarray, tool_emb: jnp.ndarray) -> jnp.ndarray:
    """Cosine similarity assuming unit-normalized rows. [Q,D]x[T,D] -> [Q,T].

    Pinned to fp32 (`Precision.HIGHEST`): this is the exact oracle and the
    manager's exact fallback, and the TPU's default precision (one bf16
    pass) misses the float32 scores by ~6e-4 at D=384 on a v5e, enough to
    reorder near-ties; HIGHEST agrees to ~2e-7.
    """
    return jnp.matmul(query_emb, tool_emb.T, precision=jax.lax.Precision.HIGHEST)


def pack_topk(scores: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """In-jit: (scores [Q, k] float32, indices [Q, k]) -> one [Q, 2k] int32
    block, the scores' bits first, then the indices.

    Packed as int32, never as float32: small indices bitcast to float32
    are subnormals, which a TPU may flush to zero."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return jnp.concatenate([bits, idx.astype(jnp.int32)], axis=1)


def unpack_topk(block, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Host side of `pack_topk`: (scores [Q, k] float32, indices [Q, k]
    int32), views of the one host copy of `block`, bit for bit."""
    block = np.asarray(block)
    return block[:, :k].view(np.float32), block[:, k:]


@functools.partial(jax.jit, static_argnames=("k",))
def topk_dense(
    query_emb: jnp.ndarray,
    tool_emb: jnp.ndarray,
    k: int,
    candidate_mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Top-k per query as one packed [Q, 2k] int32 block (`pack_topk`;
    `unpack_topk` gives (scores, indices)). candidate_mask: [Q,T] {0,1} or
    None.

    The device ops carry their step in the op metadata (`score/`, `topk/`)."""
    with jax.named_scope("score"):
        sims = similarities(query_emb, tool_emb)
        if candidate_mask is not None:
            sims = jnp.where(candidate_mask > 0, sims, NEG_INF)
    with jax.named_scope("topk"):
        scores, idx = jax.lax.top_k(sims, k)
    return pack_topk(scores, idx)


def rank_dense(
    query_emb: np.ndarray,
    tool_emb: np.ndarray,
    k: int,
    candidate_mask: np.ndarray | None = None,
) -> np.ndarray:
    """Numpy convenience wrapper returning indices only."""
    block = topk_dense(
        jnp.asarray(query_emb),
        jnp.asarray(tool_emb),
        k,
        None if candidate_mask is None else jnp.asarray(candidate_mask),
    )
    return unpack_topk(block, k)[1]
