"""Chip smoke: drive the gateway and its backend pool once on one TPU chip.

  python chip_smoke.py

One process, one chip, the entry points a user calls. Phases:

  device     JAX must report a TPU. There is no CPU path: elsewhere the
             script exits non-zero before any other phase.
  precision  one query block scored against the 2,413-tool table at JAX's
             default matmul precision and at HIGHEST, each against the host
             float32 oracle: which precision agrees with an exact router.
  router     `launch.serve.build_router` on the ToolBench-scale table (2,413
             tools) and on a 100,000-tool registry (`scale_tool_corpus`);
             N_QUERIES queries through `SemanticRouter.route_batch` on the
             pallas and dense backends, with the MLP re-ranker off and on.
             Every top-K the index layer serves is checked against a host
             NumPy float32 oracle on the table snapshot of the reported
             version: served scores within EPS of the exact ones, and
             served sets equal to the exact top-K except among candidates
             whose exact scores lie within EPS of the k-th. The index must
             serve every batch itself (no build failure, no exact fallback,
             path `index:<backend>`).
  pool       `launch.serve.main` with full-width qwen2.5-3b (no --smoke) on a
             2,413-tool table behind the pallas backend: a few requests,
             a few new tokens each; it raises on a non-finite logit.

Each phase prints what it found. A phase that fails is reported with its
traceback and the remaining phases still run, so one run shows every
fault; then the script exits non-zero. Only when every check passed does
it print, as its last line, `{"ok": true, "device": {...}}` with the
platform, device kind and device count as JAX reports them.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"
SEED = 0
K = 5
N_QUERIES = 256  # per (scale, backend, re-ranker) configuration
ROUTE_BATCH = 64
REGISTRY_TOOLS = 100_000
# about twice the worst-case float32 rounding of a 384-long dot product of
# unit rows (384 * 2**-24 ~ 2.3e-5): once on the device, once in the oracle
EPS = 5e-5
POOL_ARGV = [
    "--arch", "qwen2.5-3b", "--backend", "pallas", "--n-tools", "2413",
    "--n-queries", "400", "--requests", "4", "--route-batch", "4",
    "--max-new-tokens", "4", "--seed", str(SEED),
]


def say(msg: str) -> None:
    print(msg, flush=True)


class CheckFailed(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


# ------------------------------------------------------------------ oracle
def check_topk(q, table, scores, idx):
    """Hold one served top-K block to the float32 oracle.

    Returns (rows whose served set is the exact top-K set, rows that differ
    only among EPS-ties of the k-th score, max |served - exact| score).
    """
    k = idx.shape[1]
    exact = np.asarray(q, np.float32) @ np.asarray(table, np.float32).T  # [Q, T]
    served_exact = np.take_along_axis(exact, idx, axis=1)
    err = float(np.max(np.abs(scores - served_exact)))
    check(err <= EPS, f"served scores differ from exact by {err:.3g} > {EPS}")
    srt = np.sort(idx, axis=1)
    check(np.all(srt[:, 1:] != srt[:, :-1]), "a served top-K repeats an index")
    check(np.all(np.diff(scores, axis=1) <= EPS), "served scores not descending")
    kth = -np.partition(-exact, k - 1, axis=1)[:, k - 1 : k]  # [Q, 1]
    check(np.all(served_exact >= kth - EPS), "served a tool outside the exact top-K")
    must = exact > kth + EPS  # strictly above every EPS-tie: must be served
    got = np.take_along_axis(must, idx, axis=1).sum(axis=1)
    check(np.all(got == must.sum(axis=1)), "missed a tool of the exact top-K")
    top = np.argpartition(-exact, k - 1, axis=1)[:, :k]
    same = np.all(np.sort(top, axis=1) == srt, axis=1)
    return int(same.sum()), int((~same).sum()), err


# ------------------------------------------------------------------ phases
def phase_precision(bench):
    import jax
    import jax.numpy as jnp

    from repro.core.retrieval import similarities
    from repro.embedding.bag_encoder import BagEncoder

    enc = BagEncoder(bench.vocab)
    table = enc.encode(bench.desc_tokens)
    q = enc.encode(bench.query_tokens[:ROUTE_BATCH])
    exact = q @ table.T
    qj, tj = jnp.asarray(q), jnp.asarray(table)
    for name, prec in (("default", None), ("highest", jax.lax.Precision.HIGHEST)):
        got = np.asarray(jnp.matmul(qj, tj.T, precision=prec))
        say(f"precision: matmul at {name} precision, max|device-exact| "
            f"{float(np.max(np.abs(got - exact)))!r}")
    err = float(np.max(np.abs(np.asarray(jax.jit(similarities)(qj, tj)) - exact)))
    say(f"precision: core.retrieval.similarities max|device-exact| {err!r}")
    check(err <= EPS, f"similarities is not exact on this device ({err:.3g} > {EPS})")


def serve_and_check(router, bench, backend, rerank):
    """Route N_QUERIES queries in ROUTE_BATCH blocks; check every index call."""
    calls = []
    inner = router.index.topk

    def recording_topk(queries, k, candidate_mask=None):
        out = inner(queries, k, candidate_mask)
        calls.append((np.asarray(queries), out, router.index.last_path()))
        return out

    router.index.topk = recording_topk
    version, table = router.db.snapshot()
    table = np.asarray(table)
    same = tied = 0
    max_err = 0.0
    batch_s = []
    for lo in range(0, N_QUERIES, ROUTE_BATCH):
        block = bench.query_tokens[lo : lo + ROUTE_BATCH]
        calls.clear()
        t0 = time.perf_counter()
        results = router.route_batch(block)
        batch_s.append(time.perf_counter() - t0)
        check(len(calls) == 1, f"{len(calls)} index calls for one batch")
        q, (scores, idx, tv), path = calls[0]
        check(path == f"index:{backend}", f"served by {path}, not index:{backend}")
        check(tv == version, f"served table v{tv}, snapshot is v{version}")
        n = len(block)
        s, t, err = check_topk(q[:n], table, np.asarray(scores)[:n], np.asarray(idx)[:n])
        same, tied, max_err = same + s, tied + t, max(max_err, err)
        for j, res in enumerate(results):
            check(res.table_version == version, "result reports another table version")
            check(len(res.tools) == K and np.all(np.isfinite(res.scores)),
                  f"query {lo + j}: {len(res.tools)} tools, scores {res.scores}")
            if rerank:
                check(set(res.tools) <= set(idx[j].tolist()),
                      f"query {lo + j}: re-ranked tools outside the index candidates")
            else:
                check(res.tools == idx[j, :K].tolist(), f"query {lo + j}: tools != index top-K")
    router.index.topk = inner
    return same, tied, max_err, batch_s


def phase_router(bench, num_tools, label, kind):
    from repro.launch.serve import build_router
    from repro.router.stages import StageSet

    for backend in ("pallas", "dense"):
        t0 = time.perf_counter()
        router, pipe = build_router(bench, "oats-s2", k=K, backend=backend,
                                    num_tools=num_tools, seed=SEED)
        build_s = time.perf_counter() - t0
        try:
            check(pipe.mlp_params is not None, "oats-s2 fit no re-ranker")
            feat = pipe.featurizer
            base_t = feat.tool_freq.shape[0]
            if len(router.db) > base_t:
                # the featurizer's per-tool rows are indexed by tool id; a
                # scaled registry's tool i is a clone of base tool i % base_t
                src = np.arange(len(router.db)) % base_t
                feat = dataclasses.replace(
                    feat, success_rate=feat.success_rate[src],
                    tool_freq=feat.tool_freq[src], tool_category=feat.tool_category[src],
                )
            for rerank in (False, True):
                if rerank:
                    router.set_stages(
                        StageSet(mlp_params=pipe.mlp_params, featurizer=feat),
                        expect_version=router.stage_version,
                    )
                same, tied, max_err, batch_s = serve_and_check(router, bench, backend, rerank)
                stats = dict(router.index.stats)
                check(stats["build_failures"] == 0, f"index build failures: {stats}")
                check(stats["served_index"] > 0 and stats["served_exact"] == 0,
                      f"index did not serve every batch: {stats}")
                say(
                    f"router[{label}, {len(router.db)} tools] backend={backend} "
                    f"rerank={'on' if rerank else 'off'} k_index="
                    f"{K * router.candidate_multiplier if rerank else K}: "
                    f"{N_QUERIES} queries in {len(batch_s)} batches, path "
                    f"index:{backend}, oracle agreement {same}/{N_QUERIES} exact sets "
                    f"+ {tied} within EPS-ties, max|served-exact| {max_err!r}, "
                    f"build {build_s!r} s, first batch {batch_s[0]!r} s (compile "
                    f"included), later batches median {float(np.median(batch_s[1:]))!r} s, "
                    f"index stats {stats}, device {kind}"
                )
        finally:
            router.close()


def phase_pool():
    from repro.launch import serve

    t0 = time.perf_counter()
    stats = serve.main(POOL_ARGV)
    check(stats is not None, "serve.main returned no latency stats")
    say(f"pool: serve.main({' '.join(POOL_ARGV)}) exited cleanly in "
        f"{time.perf_counter() - t0!r} s (prefill and decode compiles included)")


def main() -> int:
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the repro package is not under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: device check failed: JAX reports {dev.platform!r}, "
              f"not a TPU", file=sys.stderr)
        return 1
    from repro.common.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    say(f"device: {dev.platform} {dev.device_kind} x{len(devices)}, "
        f"jax {jax.__version__}, compile cache {cache_dir}")

    from repro.data.benchmarks import make_toolbench_like

    bench = make_toolbench_like(seed=SEED)
    failures = []
    phases = [
        ("precision", phase_precision, (bench,)),
        ("router/paper", phase_router, (bench, 0, "paper", dev.device_kind)),
        ("router/registry", phase_router,
         (bench, REGISTRY_TOOLS, "registry", dev.device_kind)),
        ("pool", phase_pool, ()),
    ]
    for name, fn, args in phases:
        t0 = time.perf_counter()
        try:
            fn(*args)
        except Exception:  # noqa: BLE001 — reported, and the run exits non-zero
            traceback.print_exc()
            failures.append(name)
            say(f"FAIL {name} after {time.perf_counter() - t0!r} s")
        else:
            say(f"ok {name} in {time.perf_counter() - t0!r} s")
    if failures:
        print(f"chip_smoke: failed phases: {failures}", file=sys.stderr)
        return 1
    say(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
