"""Pallas TPU kernel: fused tool-similarity + running top-K.

The paper's serving hot spot (embed -> dot-products -> top-K, §4.1) for
routers co-located with TPU pods. TPU-native design (DESIGN.md §4):

  * the tool table streams HBM->VMEM in [BLOCK_T, D] tiles; D is padded to a
    lane multiple (384 -> 512) so the q @ tile^T contraction runs on the MXU;
  * a running top-K (scores + indices) lives in VMEM scratch across the tool
    grid axis — one pass over the table, no global [Q, T] score matrix is
    ever materialized (the jnp reference writes Q*T floats to HBM; at
    T=100k tools that is the difference between streaming and spilling);
  * the merge is sort-free, because Mosaic has no sort: K rounds of "row
    max, lowest column index among the maxima" over the carried top-K and
    the tile's scores. Round r only admits candidates that rank strictly
    after round r-1's winner in (score descending, index ascending) order,
    so no candidate array is rewritten between rounds, and ties resolve to
    the lowest index exactly like `lax.top_k`. The cost is K passes of
    elementwise VPU work over the [BLOCK_Q, K + BLOCK_T] candidates.
  * the contraction is pinned to fp32 (`Precision.HIGHEST`): the kernel's
    contract is exact scores, and a bf16 MXU pass would reorder near-ties.

Grid: (q_blocks, t_blocks), t innermost so the scratch carry is sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.retrieval import NEG_INF

__all__ = ["topk_sim_pallas", "BLOCK_Q", "BLOCK_T"]

BLOCK_Q = 128
BLOCK_T = 512
# the canonical padding sentinel: the gateway filters selected tools by
# `score > NEG_INF / 2`, so the kernel's padding mask must use the SAME
# constant or padded slots could surface as results
NEG = NEG_INF


def _kernel(q_ref, t_ref, vals_out, idx_out, vals_s, idx_s, *, k: int, n_tools: int):
    ti = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(ti == 0)
    def _init():
        vals_s[...] = jnp.full_like(vals_s, NEG)
        idx_s[...] = jnp.zeros_like(idx_s)

    q = q_ref[...]  # [BQ, D]
    t = t_ref[...]  # [BT, D]
    scores = jax.lax.dot_general(
        q, t, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # [BQ, BT]
    base = ti * BLOCK_T
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1) + base
    # mask padding rows of the table (T padded up to a BLOCK_T multiple)
    scores = jnp.where(col < n_tools, scores, NEG)

    # carried entries come from earlier tiles, so every (score, index) pair
    # that can win is distinct (the initial NEG slots lose to the first
    # tile's real columns, of which there are at least K)
    carry_v, carry_i = vals_s[...], idx_s[...]  # [BQ, K]
    slot = jax.lax.broadcasted_iota(jnp.int32, carry_v.shape, 1)
    big = jnp.int32(2**31 - 1)
    new_v, new_i = carry_v, carry_i
    prev_v = jnp.full((carry_v.shape[0], 1), jnp.inf, jnp.float32)
    prev_i = jnp.full((carry_v.shape[0], 1), -1, jnp.int32)
    for r in range(k):
        # candidates ranked after the previous winner: lower score, or the
        # same score at a higher index
        ok_c = (carry_v < prev_v) | ((carry_v == prev_v) & (carry_i > prev_i))
        ok_s = (scores < prev_v) | ((scores == prev_v) & (col > prev_i))
        m = jnp.maximum(
            jnp.max(jnp.where(ok_c, carry_v, -jnp.inf), axis=1, keepdims=True),
            jnp.max(jnp.where(ok_s, scores, -jnp.inf), axis=1, keepdims=True),
        )
        sel = jnp.minimum(
            jnp.min(jnp.where(ok_c & (carry_v == m), carry_i, big), axis=1, keepdims=True),
            jnp.min(jnp.where(ok_s & (scores == m), col, big), axis=1, keepdims=True),
        )
        new_v = jnp.where(slot == r, m, new_v)
        new_i = jnp.where(slot == r, sel, new_i)
        prev_v, prev_i = m, sel
    vals_s[...] = new_v
    idx_s[...] = new_i

    @pl.when(ti == nt - 1)
    def _emit():
        vals_out[...] = vals_s[...]
        idx_out[...] = idx_s[...]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_sim_pallas(
    queries: jnp.ndarray,  # [Q, D]
    table: jnp.ndarray,  # [T, D]
    k: int,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    q, d = queries.shape
    t = table.shape[0]
    # pad every axis to hardware-aligned multiples
    qp = (-q) % BLOCK_Q
    tp = (-t) % BLOCK_T
    dp = (-d) % 128
    if qp or dp:
        queries = jnp.pad(queries, ((0, qp), (0, dp)))
    if tp or dp:
        table = jnp.pad(table, ((0, tp), (0, dp)))
    qq, tt, dd = q + qp, t + tp, d + dp

    grid = (qq // BLOCK_Q, tt // BLOCK_T)
    kernel = pl.pallas_call(
        functools.partial(_kernel, k=k, n_tools=t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_Q, dd), lambda qi, ti: (qi, 0)),
            pl.BlockSpec((BLOCK_T, dd), lambda qi, ti: (ti, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_Q, k), lambda qi, ti: (qi, 0)),
            pl.BlockSpec((BLOCK_Q, k), lambda qi, ti: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qq, k), jnp.float32),
            jax.ShapeDtypeStruct((qq, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((BLOCK_Q, k), jnp.float32),
            pltpu.VMEM((BLOCK_Q, k), jnp.int32),
        ],
        interpret=interpret,
    )
    # one op scores and selects: its metadata names both steps
    with jax.named_scope("score"), jax.named_scope("topk"):
        vals, idx = kernel(queries, table)
    return vals[:q], idx[:q]
