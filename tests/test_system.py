"""End-to-end system behaviour: the paper's headline claims on the synthetic
benchmarks (directional reproduction, DESIGN.md §2) and the serve launcher."""
import numpy as np
import pytest

from repro.core.evaluate import BenchmarkEvaluator


@pytest.fixture(scope="module")
def mt_results(small_bench_factory=None):
    from repro.data.benchmarks import make_metatool_like
    bench = make_metatool_like(n_tools=120, n_queries=1200)
    ev = BenchmarkEvaluator(bench)
    return {m: ev.rankings_for(m) for m in ("random", "bm25", "se", "oats-s1")}


def test_ordering_matches_paper_table4(mt_results):
    """MetaTool ordering: random < bm25 < se < oats-s1 (Table 4)."""
    n = {k: v.metrics["ndcg@5"] for k, v in mt_results.items()}
    assert n["random"] < n["bm25"] < n["se"] < n["oats-s1"]


def test_s1_gain_is_large_on_dense_outcomes(mt_results):
    """The paper's core claim: big NDCG gain at zero serving cost."""
    gain = mt_results["oats-s1"].metrics["ndcg@5"] - mt_results["se"].metrics["ndcg@5"]
    assert gain > 0.04


def test_subtask_breakdown_present(mt_results):
    r = mt_results["oats-s1"]
    assert set(r.per_subtask) == {"similar", "scenario", "reliability", "multi"}
    # 'similar' (hard negatives) is the hardest split for static embeddings
    se = mt_results["se"].per_subtask
    assert se["similar"]["ndcg@5"] <= se["scenario"]["ndcg@5"] + 0.05


def test_serve_launcher_end_to_end():
    from repro.launch.serve import main
    stats = main([
        "--arch", "qwen2.5-3b", "--smoke", "--requests", "3",
        "--max-new-tokens", "2", "--n-tools", "40", "--n-queries", "120",
    ])
    assert stats.p50_ms < 1000  # sanity; CPU smoke


def test_train_launcher_loss_drops():
    from repro.launch.train import main
    history = main([
        "--arch", "hymba-1.5b", "--smoke", "--steps", "12",
        "--batch-size", "2", "--seq-len", "64",
    ])
    assert history[-1]["loss"] <= history[0]["loss"] + 0.05


def test_build_router_raises_when_chosen_index_never_serves(small_bench, monkeypatch):
    """A backend whose build fails must stop the launcher, not leave the
    exact dense fallback serving under the backend's name."""
    from repro.index import manager
    from repro.launch.serve import build_router

    real = manager._build_backend

    def failing_for_live_versions(kind, table, version, **opts):
        if version >= 0:  # the constructor's validation build (-1) passes
            raise RuntimeError("injected build failure")
        return real(kind, table, version, **opts)

    monkeypatch.setattr(manager, "_build_backend", failing_for_live_versions)
    with pytest.raises(RuntimeError, match="never became fresh"):
        build_router(small_bench, "oats-s1", backend="pallas")
