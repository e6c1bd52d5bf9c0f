"""Jit'd public wrapper for the fused similarity+top-K op.

`use_pallas=None` auto-selects: the Pallas kernel on TPU backends, the jnp
reference elsewhere (on the CPU, tests run the kernel body with
interpret=True).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.topk_sim.kernel import topk_sim_pallas
from repro.kernels.topk_sim.ref import topk_sim_ref

__all__ = ["topk_sim"]


def topk_sim(
    queries: jnp.ndarray,
    table: jnp.ndarray,
    k: int,
    use_pallas: bool | None = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        return topk_sim_pallas(queries, table, k, interpret=interpret)
    return topk_sim_ref(queries, table, k)
