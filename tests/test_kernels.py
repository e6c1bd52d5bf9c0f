"""Per-kernel allclose vs the pure-jnp oracle, swept over shapes and dtypes
(interpret=True executes the Pallas body on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas
from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.kernels.topk_sim.kernel import topk_sim_pallas
from repro.kernels.topk_sim.ref import topk_sim_ref

RNG = np.random.default_rng(0)


def _unit(x):
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


# ------------------------------------------------------------------ topk_sim
@pytest.mark.parametrize(
    "q,t,d,k",
    [(7, 199, 384, 5), (1, 50, 384, 10), (128, 2413, 384, 25), (33, 513, 256, 3)],
)
def test_topk_sim_shapes(q, t, d, k):
    qe = _unit(RNG.normal(size=(q, d))).astype(np.float32)
    te = _unit(RNG.normal(size=(t, d))).astype(np.float32)
    rv, ri = topk_sim_ref(jnp.asarray(qe), jnp.asarray(te), k)
    pv, pi = topk_sim_pallas(jnp.asarray(qe), jnp.asarray(te), k, interpret=True)
    np.testing.assert_allclose(np.asarray(rv), np.asarray(pv), atol=1e-5)
    assert (np.asarray(ri) == np.asarray(pi)).all()


def test_topk_sim_tie_handling():
    """Rows with BITWISE-tied scores spanning the BLOCK_T tile boundary:
    kernel and ref must both resolve ties to the LOWEST index (the kernel's
    sort-free merge takes the lowest index among tied maxima, and carried
    earlier-tile candidates have lower indices than the current tile's,
    matching lax.top_k's tie order) — pinned before the Pallas path serves
    traffic. One-hot table rows make every duplicate's dot product a single
    float term, so ties are exact regardless of GEMM summation order
    (duplicated *dense* rows can differ in the last ulp across column
    blocks and would not actually tie)."""
    d = 128
    base = np.zeros((9, d), np.float32)
    base[np.arange(9), np.arange(9)] = 1.0  # unit one-hot rows
    te = np.tile(base, (70, 1))  # 630 rows: exact ties across 2 tiles
    qe = _unit(RNG.normal(size=(4, d))).astype(np.float32)
    rv, ri = topk_sim_ref(jnp.asarray(qe), jnp.asarray(te), 8)
    pv, pi = topk_sim_pallas(jnp.asarray(qe), jnp.asarray(te), 8, interpret=True)
    np.testing.assert_allclose(np.asarray(rv), np.asarray(pv), atol=1e-6)
    assert (np.asarray(ri) == np.asarray(pi)).all()
    # all 70 copies of each query's best one-hot row tie at the max score,
    # so lowest-index-first tie order means the top-8 must be exactly the 8
    # lowest-indexed copies of that row: best, best+9, ..., best+63
    best = np.argmax(qe[:, :9], axis=1)  # score of one-hot row r is qe[:, r]
    expected = best[:, None] + 9 * np.arange(8)[None, :]
    np.testing.assert_array_equal(np.asarray(pi), expected)


@pytest.mark.parametrize("t,k", [(513, 10), (37, 20), (512, 5)])
def test_topk_sim_padded_tail_masking(t, k):
    """T is padded up to a BLOCK_T multiple inside the kernel; the padded
    tail must never surface as an index or a score. t=513 leaves a 511-row
    padded tail in tile 2; t=37 leaves a 475-row tail in a single tile."""
    qe = _unit(RNG.normal(size=(6, 384))).astype(np.float32)
    te = _unit(RNG.normal(size=(t, 384))).astype(np.float32)
    rv, ri = topk_sim_ref(jnp.asarray(qe), jnp.asarray(te), k)
    pv, pi = topk_sim_pallas(jnp.asarray(qe), jnp.asarray(te), k, interpret=True)
    pi, pv = np.asarray(pi), np.asarray(pv)
    assert ((pi >= 0) & (pi < t)).all()  # no padded-row index leaks
    assert (pv > -1e29).all()  # no NEG sentinel leaks (k <= t real rows)
    np.testing.assert_allclose(np.asarray(rv), pv, atol=1e-5)
    assert (np.asarray(ri) == pi).all()


@given(st.integers(1, 40), st.integers(30, 200), st.integers(1, 8), st.integers(0, 99))
@settings(max_examples=15, deadline=None)
def test_topk_sim_property(q, t, k, seed):
    rng = np.random.default_rng(seed)
    qe = _unit(rng.normal(size=(q, 64))).astype(np.float32)
    te = _unit(rng.normal(size=(t, 64))).astype(np.float32)
    rv, _ = topk_sim_ref(jnp.asarray(qe), jnp.asarray(te), k)
    pv, pi = topk_sim_pallas(jnp.asarray(qe), jnp.asarray(te), k, interpret=True)
    # scores agree and are sorted descending; indices in range
    np.testing.assert_allclose(np.asarray(rv), np.asarray(pv), atol=1e-5)
    pv = np.asarray(pv)
    assert (np.diff(pv, axis=1) <= 1e-6).all()
    assert ((np.asarray(pi) >= 0) & (np.asarray(pi) < t)).all()


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize(
    "bh,sq,skv,hd,causal,window,q_offset",
    [
        (2, 128, 128, 64, True, 0, 0),
        (3, 200, 200, 64, True, 0, 0),
        (2, 256, 256, 128, True, 64, 0),
        (1, 1, 300, 64, True, 0, 299),  # decode step
        (2, 128, 128, 80, False, 0, 0),  # cross-attention, padded head dim
        (1, 96, 160, 64, True, 0, 64),  # chunked prefill continuation
    ],
)
def test_flash_attention_shapes(bh, sq, skv, hd, causal, window, q_offset):
    q = RNG.normal(size=(bh, sq, hd)).astype(np.float32)
    k = RNG.normal(size=(bh, skv, hd)).astype(np.float32)
    v = RNG.normal(size=(bh, skv, hd)).astype(np.float32)
    ref = attention_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal, window, q_offset)
    got = flash_attention_pallas(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        causal=causal, window=window, q_offset=q_offset, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), atol=2e-5)


def test_flash_attention_bf16():
    q = RNG.normal(size=(2, 128, 64)).astype(np.float32)
    k = RNG.normal(size=(2, 128, 64)).astype(np.float32)
    v = RNG.normal(size=(2, 128, 64)).astype(np.float32)
    ref = attention_ref(*(jnp.asarray(x, jnp.bfloat16) for x in (q, k, v)))
    got = flash_attention_pallas(
        *(jnp.asarray(x, jnp.bfloat16) for x in (q, k, v)), interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(ref, np.float32), np.asarray(got, np.float32), atol=3e-2
    )


# ----------------------------------------------------------------- ssd scan
@pytest.mark.parametrize(
    "b,s,h,p,g,n,chunk",
    [(2, 256, 4, 64, 1, 128, 64), (1, 512, 8, 64, 2, 64, 128), (2, 128, 2, 32, 1, 16, 32)],
)
def test_ssd_scan_shapes(b, s, h, p, g, n, chunk):
    x = RNG.normal(size=(b, s, h, p)).astype(np.float32)
    dt = (0.1 + 0.5 * RNG.random((b, s, h))).astype(np.float32)
    a_log = (RNG.normal(size=(h,)) * 0.5).astype(np.float32)
    bm = (RNG.normal(size=(b, s, g, n)) * 0.3).astype(np.float32)
    cm = (RNG.normal(size=(b, s, g, n)) * 0.3).astype(np.float32)
    ry, rst = ssd_scan_ref(*map(jnp.asarray, (x, dt, a_log, bm, cm)), chunk)
    py, pst = ssd_scan_pallas(*map(jnp.asarray, (x, dt, a_log, bm, cm)), chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(ry), np.asarray(py), atol=1e-3)
    np.testing.assert_allclose(np.asarray(rst), np.asarray(pst), atol=1e-3)


def test_ssd_scan_matches_sequential_recurrence():
    """Chunked SSD == naive per-token recurrence (the SSM decode path)."""
    b, s, h, p, n, chunk = 1, 64, 2, 16, 8, 16
    x = RNG.normal(size=(b, s, h, p)).astype(np.float32)
    dt = (0.1 + 0.3 * RNG.random((b, s, h))).astype(np.float32)
    a_log = (RNG.normal(size=(h,)) * 0.3).astype(np.float32)
    bm = (RNG.normal(size=(b, s, 1, n)) * 0.3).astype(np.float32)
    cm = (RNG.normal(size=(b, s, 1, n)) * 0.3).astype(np.float32)
    y_k, st_k = ssd_scan_pallas(*map(jnp.asarray, (x, dt, a_log, bm, cm)), chunk, interpret=True)
    # naive recurrence
    a = -np.exp(a_log)
    state = np.zeros((b, h, p, n), np.float64)
    ys = np.zeros((b, s, h, p), np.float64)
    for t in range(s):
        da = np.exp(dt[:, t] * a)  # [b,h]
        bx = np.einsum("bh,bhn,bhp->bhpn", dt[:, t], bm[:, t, 0][:, None, :].repeat(h, 1), x[:, t])
        state = state * da[:, :, None, None] + bx
        ys[:, t] = np.einsum("bhn,bhpn->bhp", cm[:, t, 0][:, None, :].repeat(h, 1), state)
    np.testing.assert_allclose(np.asarray(y_k), ys, atol=1e-3)
    np.testing.assert_allclose(np.asarray(st_k), state, atol=1e-3)
