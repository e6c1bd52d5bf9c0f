"""The table reference rebuilds the program's deployed table from the data and the seed."""
import functools
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.references import oats_table, static_table  # noqa: E402

CONFIG = json.loads((ROOT / "bench/configs/toolbench-16464.json").read_text())


@pytest.mark.parametrize("seed", [7, 2**32 + 9])
def test_reference_matches_the_programs_float32_fit_and_growth(seed):
    """On the CPU the program's fit is float32, as the configuration states."""
    from bench import cell
    from repro.data import benchmarks

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(benchmarks, "make_toolbench_like", functools.partial(
            benchmarks.make_toolbench_like, n_tools=300, n_queries=80))
        dep = cell.build(dict(CONFIG, tools=1000, num_tools=1000), seed)
    dep.router.close()
    d = dep.data
    tables = oats_table.build(d.vocab.word_vecs, d.desc_tokens, d.query_tokens, d.relevant,
                              d.candidates, d.train_idx, 1000, seed, CONFIG["table"], 5)
    assert oats_table.table_off_pct(dep.table, tables) == 0.0
    assert min(np.abs(dep.table - t).max() for t in tables) < 1e-6


@pytest.mark.parametrize("seed", [11, 2**31 + 7])
def test_static_reference_matches_the_programs_static_table(seed):
    from bench import cell
    from repro.data import benchmarks

    config = json.loads((ROOT / "bench/configs/toolbench-2413-static.json").read_text())
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(benchmarks, "make_toolbench_like", functools.partial(
            benchmarks.make_toolbench_like, n_tools=300, n_queries=80))
        dep = cell.build(dict(config, tools=300), seed)
    dep.router.close()
    d = dep.data
    tables = static_table.build(d.vocab.word_vecs, d.desc_tokens, d.query_tokens, d.relevant,
                                d.candidates, d.train_idx, 300, seed, config["table"], 5)
    assert static_table.table_off_pct(dep.table, tables) == 0.0
    assert np.abs(dep.table - tables[0]).max() < 1e-6
    # an OATS-S1 table in its place is off wherever the refinement moved a row
    refined = oats_table.build(d.vocab.word_vecs, d.desc_tokens, d.query_tokens, d.relevant,
                               d.candidates, d.train_idx, 300, seed, CONFIG["table"], 5)[0]
    assert static_table.table_off_pct(refined.astype(np.float32), tables) > 1.0


def test_growth_keeps_the_first_rows_and_unit_rows():
    rng = np.random.default_rng(0)
    base = rng.standard_normal((30, 384))
    base /= np.linalg.norm(base, axis=1, keepdims=True)
    big = oats_table.tile(base, 100, seed=2**31 + 3, noise=0.02)
    assert big.shape == (100, 384)
    np.testing.assert_array_equal(big[:30], base)
    np.testing.assert_allclose(np.linalg.norm(big, axis=1), 1.0, rtol=1e-12)
    # clone i stays near its source row i mod 30, and the draws come from the seed
    assert np.all(np.einsum("nd,nd->n", big[30:], base[np.arange(30, 100) % 30]) > 0.9)
    np.testing.assert_array_equal(big, oats_table.tile(base, 100, seed=2**31 + 3, noise=0.02))
    assert oats_table.rows_off(big, oats_table.tile(base, 100, seed=1, noise=0.02)) == 70
    assert oats_table.tile(base, 30, seed=1, noise=0.02) is base


def test_rows_off_counts_rows_past_the_tolerance():
    ref = np.zeros((10, 4))
    t = ref.astype(np.float32)
    t[2, 1] = 0.5 * oats_table.ROW_TOL  # rounding: not off
    t[5, 3] = 2.0 * oats_table.ROW_TOL
    t[7, 0] = -1.0
    assert oats_table.rows_off(t, ref) == 2
    assert oats_table.table_off_pct(t, [ref]) == 20.0
    assert oats_table.table_off_pct(t, [ref, t.astype(np.float64)]) == 0.0  # the nearer table
    assert oats_table.rows_off(t[:9], ref) == 9  # another shape is wholly off


def test_refine_moves_only_tools_with_positives():
    rng = np.random.default_rng(3)
    table = rng.standard_normal((12, 8))
    table /= np.linalg.norm(table, axis=1, keepdims=True)
    q = rng.standard_normal((20, 8))
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    rel = np.zeros((20, 12))
    rel[np.arange(20), np.arange(20) % 6] = 1.0  # tools 6..11 have no positives
    out = oats_table.refine(table, q, rel, None, 0.3, 0.1, 3, 0.5, 5)
    np.testing.assert_allclose(np.linalg.norm(out, axis=1), 1.0, rtol=1e-12)
    assert oats_table.rows_off(out[:6], table[:6]) == 6
    np.testing.assert_allclose(out[6:], table[6:], atol=1e-12)
    # each moved tool comes nearer the centroid of its positives
    cent = (rel.T @ q)[:6]
    assert np.all(np.einsum("nd,nd->n", out[:6], cent) > np.einsum("nd,nd->n", table[:6], cent))
