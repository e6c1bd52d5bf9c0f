"""The index call's one copy back: the top-K as one packed block.

* `pack_topk` / `unpack_topk` keep every bit: `NEG_INF`, -0.0, subnormal
  scores and small indices;
* each device path's round trip (dense, Pallas-interpret, the exact
  fallback with a mask, a mask that admits fewer than k tools, the re-rank
  width k x multiplier) returns (scores, indices) bit-identical to the
  unpacked program;
* every index call makes exactly one device-to-host copy, and one
  host-to-device copy (two with a mask), through the gateway's
  `index_transfers_total` too;
* the round trip hands the jit its NumPy inputs and returns the block of
  the program called on device arrays, bit for bit, on every device path,
  masked or not, Q 1/3/64, k and the re-rank candidate count;
* the bytes counted up are those of the dtypes that cross (an int64 mask
  narrows to int32).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.bucketing import pad_amount
from repro.core.retrieval import NEG_INF, pack_topk, similarities, topk_dense, unpack_topk
from repro.index import ToolIndexManager
from repro.index import manager as manager_mod
from repro.index.pallas_backend import topk_sim_packed
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


# ------------------------------------------------- NumPy inputs to the jit

GATEWAY_K = 5  # the benchmark cells' k
PATHS = {  # the three device paths, and whether each takes a mask
    "dense": (False, True),
    "pallas": (False,),  # masked batches go to the exact fallback
    "exact-fallback": (False, True),
}
PATH_CASES = [
    pytest.param(path, masked, q, c, id=f"{path}-{'mask' if masked else 'nomask'}-q{q}-k{c}")
    for path, masks in PATHS.items()
    for masked in masks
    for q in (1, 3, 64)
    for c in (GATEWAY_K, GATEWAY_K * MULT)  # k, and the re-rank candidate count
]


def _manager(path, monkeypatch):
    opts = PALLAS if path == "pallas" else None
    manager = ToolIndexManager(_db(), backend="dense" if path == "dense" else "pallas",
                               backend_opts=opts, async_rebuild=False, metrics=False)
    if path == "exact-fallback":
        # the next table's index build fails: every batch, masked or not,
        # is served by the manager's exact fallback
        def broken(*args, **kwargs):
            raise RuntimeError("index build failed")

        monkeypatch.setattr(manager_mod, "_build_backend", broken)
        manager.db.swap_table(np.roll(manager.db.embeddings, 1, axis=0))
        assert manager.stats["build_failures"] >= 1
    return manager


def _device_program(path, q, t, c, mask):
    """The same program, called on device arrays."""
    if path == "pallas":
        return topk_sim_packed(q, t, c, use_pallas=True, interpret=True)
    return topk_dense(q, t, c, mask)


@pytest.mark.parametrize("path,masked,q,c", PATH_CASES)
def test_numpy_round_trip_is_the_device_program_bit_for_bit(
        path, masked, q, c, monkeypatch):
    manager = _manager(path, monkeypatch)
    queries = _queries(q, seed=q)
    mask = _mask(q, (T // 2, 9, c, 1)) if masked else None
    spans = SpanRecorder()
    with spans.bound():
        scores, idx, _ = manager.topk(queries, c, mask)
    assert manager.last_path() == ("exact" if path == "exact-fallback" else f"index:{path}")
    assert (spans.d2h_copies, spans.h2d_copies) == (1, 1 + masked)
    _, table = manager.db.snapshot()
    want = _device_program(path, jnp.asarray(queries), jnp.asarray(table), c,
                           None if mask is None else jnp.asarray(mask))
    block = np.concatenate([scores.view(np.int32), idx], axis=1)
    assert np.array_equal(block, np.asarray(want))


def _router(reg, backend="dense", opts=None):
    return SemanticRouter(
        _db(), lambda tok: np.bincount(np.asarray(tok) % D, minlength=D).astype(np.float32),
        k=K, backend=backend, backend_opts=opts, metrics=reg,
    )


@pytest.mark.parametrize("mask_dtype", [np.int32, np.int64, np.bool_])
def test_h2d_bytes_are_those_of_the_dtypes_that_cross(mask_dtype):
    batches = [[np.arange(j, j + 4) for j in range(n)] for n in (1, 3, 5, 2)]
    reference = _router(MetricsRegistry())
    want = [reference.route_batch(b) for b in batches]
    want.append(reference.route_batch(batches[-1], np.ones((2, T), np.int32)))
    reg = MetricsRegistry()
    router = _router(reg)
    got = [router.route_batch(b) for b in batches]
    got.append(router.route_batch(batches[-1], np.ones((2, T), mask_dtype)))
    calls = len(got)
    assert reg.counter("index_transfers_total", dir="d2h").value() == calls
    assert reg.counter("index_transfers_total", dir="h2d").value() == calls + 1
    # float32 queries, the mask as the jit takes it (int64 narrows to
    # int32 without x64)
    q_rows = sum(len(b) + pad_amount(len(b)) for b in batches + [batches[-1]])
    mask_rows = 2 + pad_amount(2)
    item = jax.dtypes.canonicalize_dtype(mask_dtype).itemsize
    assert item == (1 if mask_dtype is np.bool_ else 4)
    assert reg.counter("index_transfer_bytes_total", dir="h2d").value() == (
        q_rows * D * 4 + mask_rows * T * item)
    # any mask dtype serves the same bits
    for g_batch, w_batch in zip(got, want):
        assert [(g.tools, g.scores) for g in g_batch] == [(w.tools, w.scores) for w in w_batch]
