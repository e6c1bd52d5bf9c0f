"""PallasBackend: the fused score+top-K TPU kernel behind the serving path.

`kernels/topk_sim` streams the tool table HBM→VMEM in tiles and carries a
running top-K in scratch, so no global [Q, T] score matrix is ever
materialized — at 100k tools that is the difference between streaming and
spilling (see the kernel's module docstring). This backend is the wiring
that was missing: `topk_sim` existed but nothing served through it.

Backend selection is `ops.topk_sim`'s: the Pallas kernel on TPU, the jitted
jnp reference elsewhere, `interpret=True` to execute the kernel body on CPU
(tests pin kernel-vs-ref parity that way; interpret mode is a correctness
harness, not a performance path). The reference path computes the identical
matmul + `lax.top_k` as `DenseBackend`, so on CPU this backend is
bit-compatible with exact dense — the cross-backend consistency test relies
on that.

No candidate-mask support: the kernel scores every table row by design
(masks would break its streaming tile layout). The manager's exact fallback
covers masked batches.

It serves through `topk_sim_packed`: `topk_sim` and the packing of its
result into one block (`core.retrieval.pack_topk`) in one jitted program,
so a call makes one device-to-host copy.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.retrieval import pack_topk
from repro.index.base import round_trip
from repro.kernels.topk_sim.ops import topk_sim

__all__ = ["PallasBackend", "topk_sim_packed"]


@functools.partial(jax.jit, static_argnames=("k", "use_pallas", "interpret"))
def topk_sim_packed(
    queries: jnp.ndarray,
    table: jnp.ndarray,
    k: int,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """`topk_sim`'s (scores, indices) as one packed [Q, 2k] int32 block."""
    return pack_topk(*topk_sim(queries, table, k, use_pallas=use_pallas,
                               interpret=interpret))


class PallasBackend:
    name = "pallas"
    supports_masks = False
    build_is_cheap = True  # one device upload; manager rebuilds inline on swap

    def __init__(
        self,
        table: np.ndarray,
        table_version: int,
        use_pallas: Optional[bool] = None,  # None: auto (TPU -> kernel)
        interpret: bool = False,  # run the kernel body on CPU (tests)
    ):
        table = np.asarray(table, np.float32)
        self.table_version = int(table_version)
        self.n_tools = table.shape[0]
        self.use_pallas = use_pallas
        self.interpret = interpret
        self._table_j = jnp.asarray(table)
        self.upload_bytes = self._table_j.nbytes

    def topk(
        self,
        queries: np.ndarray,
        k: int,
        candidate_mask: Optional[np.ndarray] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        assert candidate_mask is None, (
            "PallasBackend scores the full table (streaming kernel, no mask "
            "support); ToolIndexManager routes masked batches to the exact "
            "fallback"
        )
        return round_trip(
            lambda q, _mask: topk_sim_packed(
                q, self._table_j, k,
                use_pallas=self.use_pallas, interpret=self.interpret,
            ),
            queries,
        )
