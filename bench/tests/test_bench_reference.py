"""The correctness comparison passes the exact top-K and fails what is not."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.references import exact_topk as ref  # noqa: E402

K = 5
# the route reference's number (the table's is `test_bench_table.py`'s)
LIMITS = {"topk_err": json.loads((ROOT / "bench/configs/toolbench-2413-static.json").read_text())[
    "limits"]["topk_err"]}


@pytest.fixture(scope="module")
def world():
    rng = np.random.default_rng(20261016)
    word_vecs = rng.standard_normal((600, 384)).astype(np.float32)
    table = rng.standard_normal((3000, 384)).astype(np.float32)
    table /= np.linalg.norm(table, axis=1, keepdims=True)
    tokens = [rng.integers(0, 600, size=int(n)) for n in rng.integers(7, 17, size=400)]
    e = np.stack([word_vecs[t].mean(axis=0) for t in tokens]).astype(np.float32)
    e /= np.linalg.norm(e, axis=1, keepdims=True)  # the program's float32 encoder
    return word_vecs, table, tokens, e


def served(scores, tokens, version=3):
    idx = np.argsort(-scores, axis=1, kind="stable")[:, :K]
    top = np.take_along_axis(scores, idx, axis=1)
    return [ref.Route(t, i.tolist(), s.tolist(), version) for t, i, s in zip(tokens, idx, top)]


def bf16(x):
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


def passes(got):
    return got["malformed"] == 0 and all(got[n] <= LIMITS[n] for n in LIMITS)


def test_exact_float32_top_k_passes(world):
    word_vecs, table, tokens, e = world
    got = ref.compare(word_vecs, table, 3, served(e @ table.T, tokens), K)
    assert got["compared"] == 400 and passes(got)
    assert got["score_err"] < 1e-6 and got["rank_gap"] == 0.0


def test_top_k_scored_in_bf16_fails(world):
    """Inputs rounded to bf16 stand in for the TPU's default precision."""
    word_vecs, table, tokens, e = world
    got = ref.compare(word_vecs, table, 3, served(bf16(e) @ bf16(table).T, tokens), K)
    assert not passes(got)
    assert got["topk_err"] > 100 * LIMITS["topk_err"]


def test_one_tool_swapped_fails(world):
    word_vecs, table, tokens, e = world
    routes = served(e @ table.T, tokens)
    r = routes[17]
    outside = next(t for t in range(3000) if t not in r.tools)
    r.tools[2] = outside  # its served score left as it was: an answer altered
    got = ref.compare(word_vecs, table, 3, routes, K)
    assert got["rank_gap"] > 1e-3 and got["score_err"] > 1e-3 and not passes(got)


def test_embedding_off_by_one_token_fails(world):
    word_vecs, table, tokens, e = world
    short = np.stack([word_vecs[t[:-1]].mean(axis=0) for t in tokens]).astype(np.float32)
    short /= np.linalg.norm(short, axis=1, keepdims=True)
    got = ref.compare(word_vecs, table, 3, served(short @ table.T, tokens), K)
    assert got["score_err"] > 1e-3 and not passes(got)


@pytest.mark.parametrize("spoil", ["repeat", "short", "version", "order", "range", "nan"])
def test_malformed_results_are_counted(world, spoil):
    word_vecs, table, tokens, e = world
    routes = served(e @ table.T, tokens[:10])
    r = routes[4]
    if spoil == "repeat":
        r.tools[1] = r.tools[0]
    elif spoil == "short":
        r.tools, r.scores = r.tools[:4], r.scores[:4]
    elif spoil == "version":
        r.table_version = 2
    elif spoil == "order":
        r.scores[0], r.scores[1] = r.scores[1] - 1.0, r.scores[0]
    elif spoil == "range":
        r.tools[0] = 3000
    else:
        r.scores[3] = float("nan")
    got = ref.compare(word_vecs, table, 3, routes, K)
    assert got["malformed"] == 1 and got["compared"] == 9


def test_ties_with_the_worst_served_tool_read_zero():
    """Tools tied with the worst served one pass the screen and read a gap of ~0."""
    rng = np.random.default_rng(5)
    word_vecs = rng.standard_normal((50, 384)).astype(np.float32)
    e = word_vecs[[1, 2, 3]].mean(axis=0)
    e /= np.linalg.norm(e)
    table = np.tile(e, (200, 1)).astype(np.float32)  # every tool scores the same
    routes = [ref.Route(np.array([1, 2, 3]), [0, 1, 2, 3, 4], [1.0] * 5, 0)]
    got = ref.compare(word_vecs, table, 0, routes, K)
    assert got["compared"] == 1 and abs(got["rank_gap"]) < 1e-6
