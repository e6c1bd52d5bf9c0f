"""A whole run with the timed path broken underneath comes out not correct.

Each run skips only the harness's look for a chip: it builds a small
ToolBench-like deployment through the program's `build_router`, drives an
open-loop window through `route_batch` on the CPU, and holds the deployed
table and every served route to the references. The faults a route cell
can have: an answer altered where the index produces it, a query token lost
where the embedding is produced, scores computed at a lower precision, half
of a batch left unanswered, and a table that is not the configuration's
(the refinement skipped, the registry grown with other noise). (A step that
returns its state unchanged and an exchange between chips left out belong
to training and to four-chip cells.)
"""
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import cell, manifest  # noqa: E402

SEED = 2_718_281_828


def small(name, tools, num_tools=0):
    c = manifest.cell(manifest.load(ROOT), name)
    c.config = dict(c.config, tools=tools, num_tools=num_tools)
    c.workload = dict(c.workload, offered_rate_per_s=300.0)
    return c


@pytest.fixture(scope="module", autouse=True)
def small_benchmark():
    """The configurations' builder at 300 tools and 80 queries."""
    from repro.data import benchmarks

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(benchmarks, "make_toolbench_like", functools.partial(
            benchmarks.make_toolbench_like, n_tools=300, n_queries=80))
        yield


@pytest.fixture(scope="module")
def small_cell():
    return small("toolbench-2413-static.steady", 300)


@pytest.fixture(scope="module")
def grown_cell():
    """The steady cell on the grown registry's configuration (an OATS-S1 fit,
    grown), at 700 rows."""
    c = small("toolbench-2413-static.steady", 700, num_tools=700)
    grown = json.loads((ROOT / "bench/configs/toolbench-16464.json").read_text())
    c.config = dict(grown, tools=700, num_tools=700)
    return c


def alter_answer(router):
    inner = router.index.topk

    def topk(queries, k, mask=None):
        scores, idx, version = inner(queries, k, mask)
        idx = np.array(idx)
        idx[0, -1] = next(t for t in range(len(router.db)) if t not in idx[0])
        return scores, idx, version

    router.index.topk = topk


def drop_token(router):
    inner = router.embed_batch_fn
    router.embed_batch_fn = lambda queries: inner([q[:-1] for q in queries])


def bf16_scores(router):
    import ml_dtypes

    inner = router.index.topk

    def topk(queries, k, mask=None):
        scores, idx, version = inner(queries, k, mask)
        return np.asarray(scores).astype(ml_dtypes.bfloat16).astype(np.float32), idx, version

    router.index.topk = topk


def half_batch(router):
    inner = router.route_batch
    router.route_batch = lambda queries: inner(queries)[: len(queries) // 2]


def skip_refine(dep):
    """The program's table without the OATS-S1 refinement the configuration states."""
    base = dep.router.db.snapshot()[1]
    from repro.embedding.bag_encoder import BagEncoder

    unrefined = BagEncoder(dep.data.vocab).encode(dep.data.desc_tokens)
    redeploy(dep, np.resize(unrefined, base.shape) if len(base) > len(unrefined) else unrefined)


def other_noise(dep):
    """The registry's clone rows grown from other draws than the seed's."""
    t = np.array(dep.table)
    n = dep.data.n_tools
    t[n:] = t[np.arange(n, len(t)) % n]
    t[n:] += 0.02 * np.random.default_rng(1).standard_normal(t[n:].shape).astype(np.float32)
    t[n:] /= np.linalg.norm(t[n:], axis=1, keepdims=True)
    redeploy(dep, t)


def redeploy(dep, table):
    dep.version = dep.router.db.swap_table(np.asarray(table, np.float32))
    dep.router.index.wait_ready(timeout_s=60.0)
    dep.table = np.asarray(dep.router.db.snapshot()[1])


def run(c, fault=None, on_dep=None):
    def build(config, seed):
        dep = cell.build(config, seed)
        if fault is not None:
            fault(dep.router)
        if on_dep is not None:
            on_dep(dep)
        return dep

    return cell.run(c, SEED, 0.5, False, time.perf_counter(), None,
                    say=lambda m: None, build_fn=build)


def test_sound_run_is_correct(small_cell):
    res = run(small_cell)
    # 150 offered; one due in the window's last moment may be left as backlog
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 145
    assert res["checks"]["compared"]["value"] == res["attempted"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"route_p50_ms", "setup_s"}
    # the compilation cache sits inside the checkout, whatever the environment names
    import jax

    assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")


def test_sound_overload_run_is_correct():
    c = small("toolbench-2413-static.overload", 300)
    c.workload = dict(c.workload, offered_rate_per_s=600.0)
    res = run(c)
    assert res["correct"] and res["failed"] == 0
    assert set(res["metrics"]) == {"routes_per_s", "setup_s"}
    assert res["metrics"]["routes_per_s"]["value"] > 0


@pytest.mark.parametrize("fault,fails", [
    (alter_answer, "topk_err"),
    (drop_token, "topk_err"),
    (bf16_scores, "topk_err"),
    (half_batch, "failed"),
])
def test_broken_path_is_not_correct(small_cell, fault, fails):
    res = run(small_cell, fault)
    assert not res["correct"]
    c = res["checks"][fails]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("fault", [skip_refine, other_noise])
def test_a_table_that_is_not_the_configurations_is_not_correct(grown_cell, fault):
    res = run(grown_cell, on_dep=fault)
    c = res["checks"]["table_off_pct"]
    assert not res["correct"] and c["value"] > c["limit"]


def test_sound_grown_registry_is_correct(grown_cell):
    res = run(grown_cell)
    assert res["correct"] and res["checks"]["table_off_pct"]["value"] == 0.0
    assert set(res["metrics"]) == {"route_p50_ms", "setup_s"}


def test_control_in_the_programs_place_is_not_correct(small_cell):
    """`bench/control.py` at a size a test holds; bf16 inputs stand in for the
    chip's HIGH, since the CPU's matmul ignores the precision flag."""
    import jax.numpy as jnp

    from bench import control

    def bf16_product(q, t):
        return jnp.matmul(q.astype(jnp.bfloat16), t.astype(jnp.bfloat16).T,
                          preferred_element_type=jnp.float32)

    res = cell.run(small_cell, SEED, 0.5, False, time.perf_counter(), None,
                   say=lambda m: None, build_fn=control.control_build(bf16_product))
    assert not res["correct"] and res["failed"] == 0
    assert res["checks"]["topk_err"]["value"] > res["checks"]["topk_err"]["limit"]


def test_control_table_in_bf16_is_not_correct(small_cell):
    """`bench/control.py`'s table part: the table held in bfloat16, scored
    exactly; the table check fails it and the route check does not."""
    import jax.numpy as jnp

    from bench import control

    res = cell.run(small_cell, SEED, 0.5, False, time.perf_counter(), None,
                   say=lambda m: None,
                   build_fn=control.control_build(control.device_product("HIGHEST"),
                                                  jnp.bfloat16))
    c = res["checks"]
    assert not res["correct"] and c["table_off_pct"]["value"] > c["table_off_pct"]["limit"]
    assert c["topk_err"]["value"] <= c["topk_err"]["limit"]


def test_table_fit_in_bf16_in_the_programs_place_is_not_correct(grown_cell):
    """The table reference's fit with bf16 products, put in the program's
    place, stands in on the CPU for the program's fit at the chip's default
    precision."""
    import ml_dtypes

    from bench.references import oats_table

    def bf16(x):
        return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float64)

    def with_bf16_fit(dep):
        d, cfg = dep.data, grown_cell.config
        tables = oats_table.build(d.vocab.word_vecs, d.desc_tokens, d.query_tokens, d.relevant,
                                  d.candidates, d.train_idx, cfg["tools"], SEED, cfg["table"],
                                  cfg["k"], product=lambda a, b: bf16(a) @ bf16(b).T)
        redeploy(dep, tables[0])

    res = run(grown_cell, on_dep=with_bf16_fit)
    c = res["checks"]["table_off_pct"]
    assert not res["correct"] and c["value"] > c["limit"]
