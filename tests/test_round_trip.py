"""The index call's one copy back: the top-K as one packed block.

* `pack_topk` / `unpack_topk` keep every bit: `NEG_INF`, -0.0, subnormal
  scores and small indices;
* each device path's round trip (dense, Pallas-interpret, the exact
  fallback with a mask, a mask that admits fewer than k tools, the re-rank
  width k x multiplier) returns (scores, indices) bit-identical to the
  unpacked program;
* every index call makes exactly one device-to-host copy, and one
  host-to-device copy (two with a mask), through the gateway's
  `index_transfers_total` too.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.retrieval import NEG_INF, pack_topk, similarities, topk_dense, unpack_topk
from repro.index import ToolIndexManager
from repro.kernels.topk_sim.kernel import topk_sim_pallas
from repro.obs import MetricsRegistry
from repro.obs.trace import SpanRecorder
from repro.router.gateway import SemanticRouter
from repro.router.tooldb import ToolRecord, ToolsDatabase

D, T, K, MULT = 16, 40, 3, 5
PALLAS = {"use_pallas": True, "interpret": True}


def _db(seed=0):
    rng = np.random.default_rng(seed)
    table = rng.standard_normal((T, D)).astype(np.float32)
    table /= np.linalg.norm(table, axis=1, keepdims=True)
    records = [ToolRecord(i, f"t{i}", np.arange(3), 0) for i in range(T)]
    return ToolsDatabase(records, table)


def _queries(q=8, seed=1):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((q, D)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("k",))
def _unpacked_dense(q, t, k, mask):
    """`topk_dense` before packing: the same program, its tuple out."""
    sims = similarities(q, t)
    if mask is not None:
        sims = jnp.where(mask > 0, sims, NEG_INF)
    return jax.lax.top_k(sims, k)


def _same_bits(got, want):
    (gs, gi), (ws, wi) = got, want
    assert gs.dtype == np.float32 and gi.dtype == np.int32
    assert gs.shape == gi.shape == np.shape(ws)
    assert np.array_equal(gs.view(np.int32), np.asarray(ws, np.float32).view(np.int32))
    assert np.array_equal(gi, np.asarray(wi))


def test_pack_unpack_keeps_every_bit():
    scores = np.array([[1.0, -0.0, NEG_INF], [1e-40, -1e-45, 0.5]], np.float32)
    idx = np.array([[0, 1, 2], [3, 2**31 - 1, 7]], np.int32)
    block = jax.jit(pack_topk)(scores, idx)
    assert block.dtype == jnp.int32 and block.shape == (2, 6)
    _same_bits(unpack_topk(block, 3), (scores, idx))


def _mask(q, admit):
    """[q, T] masks; row r admits `admit[r % len(admit)]` tools."""
    m = np.zeros((q, T), np.int32)
    for r in range(q):
        m[r, (np.arange(admit[r % len(admit)]) * 7 + r) % T] = 1
    return m


# (backend, backend opts, candidate count, masks admit per row or None)
CASES = {
    "dense": ("dense", None, K, None),
    "dense-rerank-width": ("dense", None, K * MULT, None),
    "pallas": ("pallas", PALLAS, K, None),
    "pallas-rerank-width": ("pallas", PALLAS, K * MULT, None),
    # pallas takes no masks: masked batches are the exact fallback's
    "exact-fallback-mask": ("pallas", PALLAS, K, (T // 2, 9)),
    "mask-fewer-than-k": ("dense", None, K, (1, 2, K, 0)),
    "exact-fallback-fewer-than-k": ("pallas", PALLAS, K * MULT, (1, 4)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_round_trip_is_bit_identical_and_one_copy_back(case):
    backend, opts, c, admit = CASES[case]
    db = _db()
    manager = ToolIndexManager(db, backend=backend, backend_opts=opts,
                               async_rebuild=False, metrics=False)
    q = _queries()
    mask = None if admit is None else _mask(len(q), admit)
    spans = SpanRecorder()
    with spans.bound():
        scores, idx, version = manager.topk(q, c, mask)
    assert version == db.table_version
    assert manager.last_path() == ("exact" if mask is not None and backend == "pallas"
                                   else f"index:{backend}")
    _, table = db.snapshot()
    qj, tj = jnp.asarray(q), jnp.asarray(table)
    if backend == "pallas" and mask is None:
        want = topk_sim_pallas(qj, tj, c, interpret=True)
    else:
        want = _unpacked_dense(qj, tj, c, None if mask is None else jnp.asarray(mask))
    _same_bits((scores, idx), want)
    if admit is not None and min(admit) < c:
        short = mask.sum(axis=1) < c
        assert np.all(scores[short, -1] == np.float32(NEG_INF))  # sentinel survives
        assert np.all(scores[~short] > NEG_INF / 2)
    assert (spans.d2h_copies, spans.h2d_copies) == (1, 1 + (mask is not None))
    assert spans.d2h_bytes == len(q) * c * 8


@pytest.mark.parametrize("masked", [False, True])
def test_topk_dense_block_is_the_unpacked_program_packed(masked):
    q, t = jnp.asarray(_queries()), jnp.asarray(np.asarray(_db().snapshot()[1]))
    mask = jnp.asarray(_mask(q.shape[0], (1, T))) if masked else None
    block = topk_dense(q, t, K, mask)
    assert block.dtype == jnp.int32 and block.shape == (q.shape[0], 2 * K)
    _same_bits(unpack_topk(block, K), _unpacked_dense(q, t, K, mask))


@pytest.mark.parametrize("backend,opts", [("dense", None), ("pallas", PALLAS)])
def test_gateway_counts_one_copy_back_per_index_call(backend, opts):
    reg = MetricsRegistry()
    router = SemanticRouter(
        _db(), lambda tok: np.bincount(np.asarray(tok) % D, minlength=D).astype(np.float32),
        k=K, backend=backend, backend_opts=opts, metrics=reg,
    )
    batches = [[np.arange(j, j + 4) for j in range(n)] for n in (1, 3, 5, 2)]
    for b in batches:
        router.route_batch(b)
    masks = np.ones((2, T), np.int32)
    router.route_batch(batches[-1], masks)  # the exact fallback, for pallas
    calls = len(batches) + 1
    assert reg.histogram("index_step_ms", step="wait").count() == calls
    assert reg.counter("index_transfers_total", dir="d2h").value() == calls
    assert reg.counter("index_transfers_total", dir="h2d").value() == calls + 1
