"""Substrate tests: benchmark generator determinism/structure, LM pipeline,
optimizers, checkpointing, BM25, encoders."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import optim
from repro.checkpoint.msgpack_ckpt import restore_checkpoint, save_checkpoint
from repro.core.baselines import BM25
from repro.data.benchmarks import SUBTASKS, make_metatool_like, make_toolbench_like
from repro.data.lm_data import LMDataConfig, synthetic_lm_batches
from repro.embedding import bag_encoder
from repro.embedding.bag_encoder import BagEncoder, pad_token_lists
from repro.embedding.transformer import EncoderConfig, encode, encoder_param_count, init_encoder


# ----------------------------------------------------------- benchmark data
def test_benchmark_determinism():
    a = make_metatool_like(seed=3, n_tools=40, n_queries=100)
    b = make_metatool_like(seed=3, n_tools=40, n_queries=100)
    assert all((x == y).all() for x, y in zip(a.desc_tokens, b.desc_tokens))
    assert all((x == y).all() for x, y in zip(a.query_tokens, b.query_tokens))
    assert (a.train_idx == b.train_idx).all()
    c = make_metatool_like(seed=4, n_tools=40, n_queries=100)
    assert any((x != y).any() for x, y in zip(a.query_tokens, c.query_tokens))


def test_benchmark_structure(small_bench):
    b = small_bench
    assert b.n_tools == 60 and b.n_queries == 600
    # 70/30 split, disjoint, covering
    assert len(b.train_idx) + len(b.test_idx) == 600
    assert len(np.intersect1d(b.train_idx, b.test_idx)) == 0
    # ground truth always inside the candidate set
    for j in range(b.n_queries):
        assert np.isin(b.relevant[j], b.candidates[j]).all()
    # subtask mix covers all four types
    assert set(np.unique(b.subtask)) == set(range(len(SUBTASKS)))
    # multi-tool queries have >=2 ground-truth tools
    for j in np.flatnonzero(b.subtask == SUBTASKS.index("multi")):
        assert len(b.relevant[j]) >= 2


def test_encoders_agree(small_bench):
    enc = BagEncoder(small_bench.vocab)
    ragged = enc.encode(small_bench.desc_tokens[:8])
    ids, mask = pad_token_lists(small_bench.desc_tokens[:8])
    padded = np.asarray(enc.encode_padded(jnp.asarray(ids), jnp.asarray(mask)))
    np.testing.assert_allclose(ragged, padded, atol=1e-5)
    np.testing.assert_allclose(np.linalg.norm(ragged, axis=1), 1.0, atol=1e-5)


def _encode_per_query(word_vecs, token_lists):
    """The per-query loop `BagEncoder.encode` replaced: its bit-exact reference."""
    out = np.zeros((len(token_lists), word_vecs.shape[1]), dtype=np.float32)
    for i, toks in enumerate(token_lists):
        if len(toks) == 0:
            continue
        v = word_vecs[np.asarray(toks)].mean(axis=0)
        n = np.linalg.norm(v)
        out[i] = v / max(n, 1e-9)
    return out


@pytest.fixture(scope="module")
def toolbench_bench():
    # the data both benchmark configurations serve: 2,413 tools, 600 queries
    return make_toolbench_like(seed=0)


def _ragged_lists(rng, n_words):
    """Short rows around an empty list, a length-1 list and a 5,000-token one."""
    rows = [rng.integers(0, n_words, rng.integers(7, 18)) for _ in range(60)]
    rows += [np.zeros(0, np.int64), np.array([3]), rng.integers(0, n_words, 5000)]
    return [rows[i] for i in rng.permutation(len(rows))]


_ENCODE_CASES = {
    "small-descriptions": lambda b, s, rng: s.desc_tokens,
    "small-queries": lambda b, s, rng: s.query_tokens,
    "toolbench-descriptions": lambda b, s, rng: b.desc_tokens,
    "toolbench-queries": lambda b, s, rng: b.query_tokens,
    "ragged-empty-one-outlier": lambda b, s, rng: _ragged_lists(rng, len(b.vocab.word_vecs)),
    "only-empty": lambda b, s, rng: [np.zeros(0, np.int64), []],
    "batch-of-one": lambda b, s, rng: b.query_tokens[:1],
    "two-longest-tie": lambda b, s, rng: [np.arange(9), np.arange(20, 29), np.arange(4)],
    "larger-than-a-block": lambda b, s, rng: [
        rng.integers(0, len(b.vocab.word_vecs), rng.integers(0, 40))
        for _ in range(3 * bag_encoder._BLOCK + 7)
    ],
    "int32-ids": lambda b, s, rng: [t.astype(np.int32) for t in b.query_tokens],
    "int64-ids": lambda b, s, rng: [t.astype(np.int64) for t in b.query_tokens],
    "mixed-dtypes-and-lists": lambda b, s, rng: [
        np.array([7, 7, 7], np.int16), [1, 2, 3, 4], np.array([9], np.uint8),
        b.query_tokens[0].astype(np.int32), [],
    ],
}


@pytest.mark.parametrize("case", sorted(_ENCODE_CASES))
def test_batched_encode_is_bit_identical_to_the_per_query_loop(
    case, toolbench_bench, small_bench
):
    bench = small_bench if case.startswith("small") else toolbench_bench
    token_lists = _ENCODE_CASES[case](
        toolbench_bench, small_bench, np.random.default_rng(7)
    )
    enc = BagEncoder(bench.vocab)
    ref = _encode_per_query(bench.vocab.word_vecs, token_lists)
    got = enc.encode(token_lists)
    assert got.dtype == np.float32 and got.shape == ref.shape
    assert np.array_equal(got, ref)
    for i, toks in enumerate(token_lists[:4]):
        assert np.array_equal(enc.encode_one(toks), ref[i])


def test_transformer_encoder_is_minilm_shaped():
    cfg = EncoderConfig()
    params = init_encoder(jax.random.PRNGKey(0), cfg)
    n = encoder_param_count(params)
    assert 21e6 < n < 24e6  # ~22M like all-MiniLM-L6-v2
    ids = np.zeros((2, 16), np.int32)
    mask = np.ones((2, 16), np.int32)
    out = encode(params, jnp.asarray(ids), jnp.asarray(mask))
    assert out.shape == (2, 384)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(out), axis=1), 1.0, atol=1e-5)


def test_bm25_prefers_exact_overlap():
    docs = [np.array([1, 2, 3, 4]), np.array([5, 6, 7, 8]), np.array([1, 9, 10, 11])]
    bm = BM25.fit(docs, vocab_size=16)
    scores = bm.scores([np.array([5, 6])])
    assert scores[0].argmax() == 1
    # rare terms outweigh common ones
    scores2 = bm.scores([np.array([1, 5])])
    assert scores2[0, 1] > scores2[0, 2]  # doc1 has rare 5; docs 0,2 share 1


# ------------------------------------------------------------- LM pipeline
def test_lm_pipeline_deterministic_and_shaped():
    from repro.configs import ARCHITECTURES
    from repro.models.config import reduced

    cfg = reduced(ARCHITECTURES["musicgen-medium"])
    it1 = synthetic_lm_batches(cfg, LMDataConfig(batch_size=2, seq_len=32, seed=1))
    it2 = synthetic_lm_batches(cfg, LMDataConfig(batch_size=2, seq_len=32, seed=1))
    b1, b2 = next(it1), next(it2)
    assert (b1["tokens"] == b2["tokens"]).all()
    assert b1["tokens"].shape == (2, 32, cfg.n_codebooks)
    assert b1["tokens"].max() < cfg.vocab_size


# --------------------------------------------------------------- optimizers
def _quadratic(p):
    return sum(jnp.sum(jnp.square(x - 3.0)) for x in jax.tree.leaves(p))


@pytest.mark.parametrize("name", ["adamw", "adam", "sgd", "adafactor"])
def test_optimizers_minimize_quadratic(name):
    opt = {
        "adamw": lambda: optim.adamw(0.1),
        "adam": lambda: optim.adam(0.1),
        "sgd": lambda: optim.sgd(0.05, momentum=0.9),
        "adafactor": lambda: optim.adafactor(0.5),
    }[name]()
    params = {"w": jnp.zeros((4, 8)), "b": jnp.zeros((8,))}
    state = opt.init(params)
    loss0 = float(_quadratic(params))

    @jax.jit
    def step(params, state):
        grads = jax.grad(_quadratic)(params)
        updates, state = opt.update(grads, state, params)
        return optim.apply_updates(params, updates), state

    for _ in range(100):
        params, state = step(params, state)
    assert float(_quadratic(params)) < 0.05 * loss0


def test_grad_clip():
    tree = {"a": jnp.full((10,), 100.0)}
    clipped, norm = optim.clip_by_global_norm(tree, 1.0)
    assert float(norm) > 1.0
    assert float(optim.global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


@given(st.integers(0, 100))
@settings(max_examples=10, deadline=None)
def test_schedules_bounded(seed):
    sched = optim.warmup_cosine(1e-3, 10, 100, floor=1e-5)
    step = jnp.asarray(seed)
    lr = float(sched(step))
    assert 0 <= lr <= 1e-3 + 1e-9


# ------------------------------------------------------------- checkpointing
def test_checkpoint_roundtrip(tmp_path):
    tree = {
        "params": {"w": np.arange(12, dtype=np.float32).reshape(3, 4)},
        "opt": [np.ones(3, np.int64), {"x": np.float32(2.5)}],
    }
    save_checkpoint(str(tmp_path), 7, tree, meta={"arch": "t"})
    step, restored, meta = restore_checkpoint(str(tmp_path))
    assert step == 7 and meta["arch"] == "t"
    np.testing.assert_array_equal(restored["params"]["w"], tree["params"]["w"])
    np.testing.assert_array_equal(restored["opt"][0], tree["opt"][0])
    # rotation: newer step wins
    save_checkpoint(str(tmp_path), 9, tree)
    assert restore_checkpoint(str(tmp_path))[0] == 9
