"""Pure-jnp oracle for the fused similarity + top-K kernel (Eq. 2)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["topk_sim_ref"]


def topk_sim_ref(
    queries: jnp.ndarray,  # [Q, D] unit rows
    table: jnp.ndarray,  # [T, D] unit rows
    k: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (scores [Q, k], indices [Q, k]) by descending similarity,
    with the contraction in fp32 like the kernel's (`Precision.HIGHEST`)."""
    with jax.named_scope("score"):
        sims = jnp.matmul(queries, table.T, precision=jax.lax.Precision.HIGHEST)
    with jax.named_scope("topk"):
        return jax.lax.top_k(sims, k)
