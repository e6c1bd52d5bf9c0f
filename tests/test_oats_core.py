"""OATS core invariants: Alg. 1 semantics, the validation gate, parameter
counts matching the paper, and the full stage pipeline."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import adapter as adapter_lib
from repro.core import reranker as reranker_lib
from repro.core.outcomes import collect_outcomes
from repro.core.pipeline import OATSPipeline, PipelineConfig, STAGE_PRESETS
from repro.core.refine import RefineConfig, refine_embeddings, refine_with_gate
from repro.embedding.bag_encoder import BagEncoder


def _unit(x):
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def _random_world(seed, q=40, t=12, d=32):
    rng = np.random.default_rng(seed)
    qe = _unit(rng.normal(size=(q, d))).astype(np.float32)
    te = _unit(rng.normal(size=(t, d))).astype(np.float32)
    rel = np.zeros((q, t), np.float32)
    rel[np.arange(q), rng.integers(0, t, q)] = 1.0
    return qe, te, rel


def test_outcome_partition_semantics():
    qe, te, rel = _random_world(0)
    logs = collect_outcomes(jnp.asarray(qe), jnp.asarray(te), jnp.asarray(rel), k=5)
    pos = np.asarray(logs.pos_mask)
    neg = np.asarray(logs.neg_mask)
    # positives are exactly the ground-truth pairs ("ground_truth" mode)
    assert (pos == rel).all()
    # negatives only where retrieved and NOT relevant
    assert (neg * rel).sum() == 0
    retrieved = np.asarray(logs.retrieved)
    for j in range(neg.shape[0]):
        for t_id in np.flatnonzero(neg[j]):
            assert t_id in retrieved[j]


@given(st.integers(0, 500))
@settings(max_examples=20, deadline=None)
def test_refined_embeddings_stay_unit_norm(seed):
    qe, te, rel = _random_world(seed)
    hist = refine_embeddings(jnp.asarray(te), jnp.asarray(qe), jnp.asarray(rel))
    final = np.asarray(hist[-1])
    norms = np.linalg.norm(final, axis=-1)
    assert np.allclose(norms, 1.0, atol=1e-5)


def test_refinement_moves_toward_positive_centroid():
    """A tool with a tight positive cluster must move toward it (Eq. 7)."""
    rng = np.random.default_rng(3)
    d = 32
    target = _unit(rng.normal(size=d))
    qe = _unit(target + 0.2 * _unit(rng.normal(size=(12, d)))).astype(np.float32)
    # tool 0 = opaque (far from its queries); tool 1 = decoy
    te = _unit(rng.normal(size=(2, d))).astype(np.float32)
    rel = np.zeros((12, 2), np.float32)
    rel[:, 0] = 1.0
    hist = refine_embeddings(jnp.asarray(te), jnp.asarray(qe), jnp.asarray(rel))
    before = float(qe.mean(0) @ te[0])
    after = float(qe.mean(0) @ np.asarray(hist[-1])[0])
    assert after > before  # pulled toward the positive centroid


def test_validation_gate_never_degrades():
    """Gate invariant (§4.1 step 5): deployed table >= static on val recall."""
    for seed in range(5):
        qe, te, rel = _random_world(seed, q=60)
        tr, va = slice(0, 45), slice(45, 60)
        res = refine_with_gate(
            jnp.asarray(te),
            jnp.asarray(qe[tr]), jnp.asarray(rel[tr]),
            jnp.asarray(qe[va]), jnp.asarray(rel[va]),
            RefineConfig(),
        )
        assert float(res.recall_after) >= float(res.recall_before) or not bool(
            res.accepted
        )
        if not bool(res.accepted):
            # rejected -> table unchanged
            assert np.allclose(np.asarray(res.embeddings), te, atol=1e-6)


def test_gate_rejects_adversarial_refinement():
    """If train labels are adversarial (shuffled), the gate must reject or at
    least not deploy a worse table."""
    qe, te, rel = _random_world(7, q=80)
    rng = np.random.default_rng(0)
    rel_shuffled = rel.copy()
    rng.shuffle(rel_shuffled, axis=0)  # train labels decorrelated from queries
    res = refine_with_gate(
        jnp.asarray(te),
        jnp.asarray(qe[:60]), jnp.asarray(rel_shuffled[:60]),
        jnp.asarray(qe[60:]), jnp.asarray(rel[60:]),
        RefineConfig(),
    )
    if bool(res.accepted):
        assert float(res.recall_after) >= float(res.recall_before)


def test_paper_parameter_counts():
    """§4.2: MLP [7,64,32,1] = 2,625 params; §4.3: adapter = 197,248."""
    mlp = reranker_lib.init_mlp(jax.random.PRNGKey(0))
    assert reranker_lib.mlp_param_count(mlp) == 2625
    ad = adapter_lib.init_adapter(jax.random.PRNGKey(0))
    assert adapter_lib.adapter_param_count(ad) == 197248


def test_adapter_starts_as_identity():
    ad = adapter_lib.init_adapter(jax.random.PRNGKey(0))
    x = _unit(np.random.default_rng(0).normal(size=(5, 384))).astype(np.float32)
    y = np.asarray(adapter_lib.adapter_apply(ad, jnp.asarray(x)))
    assert np.allclose(x, y, atol=1e-6)


def test_pipeline_stage_presets(small_bench):
    enc = BagEncoder(small_bench.vocab)
    for stage in ("oats-s1", "oats-s2"):
        pipe = OATSPipeline.fit(
            small_bench, PipelineConfig(stages=STAGE_PRESETS[stage]), enc
        )
        test_idx = small_bench.test_idx[:20]
        rk = pipe.rank(
            [small_bench.query_tokens[i] for i in test_idx],
            5,
            small_bench.candidate_mask()[test_idx],
        )
        assert rk.shape == (20, 5)
        # rankings must respect candidate sets
        cand = small_bench.candidate_mask()[test_idx]
        for j in range(20):
            assert cand[j][rk[j]].all()


def test_s1_improves_over_static(small_bench):
    """The paper's core claim, on the dense-outcome benchmark."""
    from repro.core.evaluate import BenchmarkEvaluator

    ev = BenchmarkEvaluator(small_bench)
    se = ev.rankings_for("se").metrics["ndcg@5"]
    s1 = ev.rankings_for("oats-s1").metrics["ndcg@5"]
    assert s1 > se + 0.02, (se, s1)


# --------------------------------------------- Alg. 1 against a plain reference

REF_ATOL = 1e-5  # float32 rounding of 4 passes on unit rows is ~1e-6; bf16 ~1e-3


def _alg1_reference(te, qe, rel, pools, alpha, beta, iterations, momentum, k):
    """Alg. 1's passes in float64 NumPy: the table after each pass."""
    unit = lambda x: x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-9)  # noqa: E731
    e, q, passes = te.astype(np.float64), qe.astype(np.float64), []
    for n in range(iterations):
        sims = q @ e.T if pools is None else np.where(pools > 0, q @ e.T, -1e30)
        neg = np.zeros_like(rel, np.float64)
        neg[np.arange(len(q))[:, None], np.argsort(-sims, axis=1, kind="stable")[:, :k]] = 1.0
        neg *= 1.0 - rel
        pos_n, neg_n = rel.sum(0), neg.sum(0)
        pos_c = rel.T @ q / np.maximum(pos_n, 1.0)[:, None]
        neg_c = neg.T @ q / np.maximum(neg_n, 1.0)[:, None]
        e_hat = unit((1 - alpha) * e + alpha * pos_c - beta * (neg_n > 0)[:, None] * neg_c)
        e_hat = np.where((pos_n > 0)[:, None], e_hat, e)
        e = unit(momentum * e + (1 - momentum) * e_hat) if n > 0 else e_hat
        passes.append(e)
    return passes


def _reference_recall(qe, table, rel, pools, k):
    sims = qe.astype(np.float64) @ table.T
    sims = sims if pools is None else np.where(pools > 0, sims, -1e30)
    top = np.argsort(-sims, axis=1, kind="stable")[:, :k]
    hits, n_rel = np.take_along_axis(rel, top, axis=1).sum(1), rel.sum(1)
    return float((hits / np.maximum(n_rel, 1))[n_rel > 0].mean())


_REF_CASES = [
    (n, mu, masked, keep, False)
    for n in (1, 2, 3, 4) for mu in (0.0, 0.5) for masked in (False, True)
    for keep in (True, False)
] + [(3, 0.5, False, True, True)]  # a bf16 fit: must come out off the reference


@pytest.mark.parametrize("iterations,momentum,masked,keep_history,bf16", _REF_CASES)
def test_refine_matches_plain_reference_pass_by_pass(
    iterations, momentum, masked, keep_history, bf16
):
    qe, te, rel = _random_world(iterations + 10 * masked, q=80, t=24)
    pools = None
    if masked:  # each query's pool: its relevant tool and about 40% of the others
        rng = np.random.default_rng(iterations)
        pools = (rng.random(rel.shape) < 0.4).astype(np.float32)
        pools[rel > 0] = 1.0
    fit, val = slice(0, 64), slice(64, 80)
    dtype = jnp.bfloat16 if bf16 else jnp.float32
    args = (jnp.asarray(te, dtype), jnp.asarray(qe[fit], dtype), jnp.asarray(rel[fit]),
            None if pools is None else jnp.asarray(pools[fit]))
    kw = dict(alpha=0.3, beta=0.1, iterations=iterations, momentum=momentum, k=5)
    ref = _alg1_reference(te, qe[fit], rel[fit], None if pools is None else pools[fit], **kw)

    hist = np.asarray(refine_embeddings(*args, keep_history=True, **kw), np.float64)
    final = np.asarray(refine_embeddings(*args, keep_history=False, **kw), np.float64)
    assert hist.shape == (iterations + 1, *te.shape)
    gaps = [np.abs(hist[p + 1] - ref[p]).max() for p in range(iterations)]
    if bf16:
        assert max(gaps) > REF_ATOL, gaps  # the tolerance tells a bf16 fit apart
        return
    np.testing.assert_array_equal(hist[0], te)
    assert max(gaps) < REF_ATOL, gaps
    np.testing.assert_allclose(final, hist[-1], atol=1e-6, rtol=0)

    res = refine_with_gate(
        *args[:3], jnp.asarray(qe[val]), jnp.asarray(rel[val]),
        RefineConfig(keep_history=keep_history, **kw), args[3],
        None if pools is None else jnp.asarray(pools[val]),
    )
    vpools = None if pools is None else pools[val]
    before = _reference_recall(qe[val], te.astype(np.float64), rel[val], vpools, 5)
    after = _reference_recall(qe[val], ref[-1], rel[val], vpools, 5)
    assert bool(res.accepted) == (after >= before)
    np.testing.assert_allclose(float(res.recall_before), before, atol=1e-6)
    np.testing.assert_allclose(float(res.recall_after), after, atol=1e-6)
    deployed = ref[-1] if after >= before else te
    np.testing.assert_allclose(np.asarray(res.embeddings), deployed, atol=REF_ATOL, rtol=0)
    assert (res.history is None) == (not keep_history)
