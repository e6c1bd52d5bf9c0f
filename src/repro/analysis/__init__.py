"""Invariant analyzer: repo-specific lint, retrace detector, lock-order checker.

The router's latency story rests on invariants generic linters cannot see:
compare-and-swap-only version swaps, atomic snapshot reads, one portable
mesh layer, one bucketing function, no device work under hot-path locks.
This package enforces them.

Three legs, run by ``scripts/ci_check.sh`` ahead of the tests; the tests
run each one too (`tests/test_analysis.py`:
``test_repo_is_clean_under_checked_in_baseline``,
``test_route_batch_stays_inside_bucket_set``,
``test_live_planes_have_no_cycles_or_dispatch_under_lock``):

* ``python -m repro.analysis [paths]`` — AST lint (this file's catalog below);
* ``python -m repro.analysis.retrace`` — runtime jit-retrace detector that
  builds a small router, sweeps batch sizes, and fails if hot-path entry
  points compile beyond the expected power-of-two bucket set;
* ``python -m repro.analysis.lockgraph`` — instrumented-lock run of a
  threaded swap/refine/stage-churn scenario, failing on lock-order cycles
  or JAX dispatch performed while holding a lock.

Rule catalog
============

mesh-api
    *What*: raw JAX mesh-context APIs (``jax.set_mesh``,
    ``jax.sharding.use_mesh``/``get_abstract_mesh``, ``jax.make_mesh``,
    ``shard_map`` imports, ``jax._src.mesh``, ``thread_resources``) used
    outside ``common/meshctx.py``.
    *Why*: these APIs drift across JAX releases; meshctx exists to pin the
    drift to one file so version bumps are a one-file diff.
    *Fix*: call ``repro.common.meshctx`` (``use_mesh``, ``make_mesh``,
    ``current_mesh``, ``axis_sizes_dict``, ``shard_map``).

cas-discipline
    *What*: ``swap_table``/``rollback``/``rollback_stages`` without
    ``expect_current=``, ``set_stages`` without ``expect_version=``.
    *Why*: a bare swap silently clobbers a concurrent deployment — the
    lost-update race the versioned stores exist to refuse (ConflictError).
    *Fix*: pass the expectation from the snapshot the change was derived
    from. Receivers named ``*registry*`` are exempt (ArtifactRegistry's
    rollback is bounded-history trimming, not a serving CAS).

snapshot-discipline
    *What*: touching another object's ``_table``/``_history``/``_stages``/
    ``_stage_history``/``_swap_listeners`` outside the owning router
    modules.
    *Why*: bypassing ``snapshot()``/``stage_set()`` can observe a
    half-completed swap and mis-attribute scores to the wrong version.
    *Fix*: read through the atomic accessors.

jit-in-function
    *What*: ``jax.jit`` applied inside a function body (call or decorator
    on a nested def).
    *Why*: each instance gets a fresh trace cache — compile cost paid per
    object instead of once per process; a multi-ms stall if it reaches the
    hot path.
    *Fix*: hoist to module scope, or baseline with justification when the
    closure is deliberate (offline training, per-process singletons).

jit-static-scalar
    *What*: a jitted function with an ``int``/``bool``/``str``-annotated
    parameter not in ``static_argnames``.
    *Why*: shape-controlling scalars become traced values (tracer errors
    or silent wrong shapes); hashable config belongs in the compile key.
    *Fix*: add to ``static_argnames``.

pow2-bucket
    *What*: hand-rolled ``1 << n.bit_length()`` bucket math outside
    ``common/bucketing.py``.
    *Why*: every jitted entry point must agree on one bucketing function,
    or the retrace detector's expected-bucket set is per-module luck.
    *Fix*: ``repro.common.bucketing.pow2_bucket`` / ``expected_buckets``.

lock-dispatch
    *What*: ``jnp.*``/``jax.*``/known-jitted/``device_put`` calls lexically
    inside ``with <lock>:`` in ``router/``, ``control/``, ``learn/``,
    ``index/``.
    *Why*: device work under a hot-path lock stalls every contending
    thread; a compile under a lock is a multi-ms p99 breach for all of
    them.
    *Fix*: compute outside the critical section, hold the lock only to
    publish (see ``ToolIndexManager._build``).

cache-version-stamp
    *What*: ``lookup_batch``/``insert_batch`` on a ``*cache*`` receiver
    without explicit ``table_version=`` AND ``stage_version=`` keywords;
    plus the lock-dispatch scan applied to the ``cache/`` package.
    *Why*: the route cache's exact-invalidation guarantee holds only if
    every entry is stamped with the snapshot its scores came from — an
    unstamped site can serve a decision from a dead table after a swap.
    The cache lock is taken per routed batch, so device work under it is
    the same p99 hazard lock-dispatch polices elsewhere.
    *Fix*: thread the versions from the same snapshot that produced the
    scores (the topk's returned version); keep cache critical sections
    numpy-only.

thread-discipline
    *What*: a ``daemon=True`` thread whose locally-defined loop lacks an
    ``except Exception`` handler, or has one that does not record the
    failure on an ``*error*``/``*exception*`` attribute.
    *Why*: a dead or flapping control/learning plane that no guard or
    health check can detect.
    *Fix*: record ``self.last_loop_error = exc`` (clear on success) where
    health checks look.

obs-discipline
    *What*: direct ``time.time()``/``time.perf_counter()``/
    ``time.monotonic()`` or ``print()`` calls in ``router/``, ``index/``,
    ``control/``, and ``learn/``.
    *Why*: recorded durations must share one monotonic source
    (wall-clock NTP slew corrupts latency histograms and controller
    cooldown/cadence arithmetic), and a serving process's stdout is not
    an operator surface — the telemetry plane (metrics/events/health) is.
    *Fix*: ``repro.obs.clock`` (``perf``/``monotonic``/``wall``/
    ``duration_ms``); publish operator-facing state to the
    ``MetricsRegistry``/``EventBus``.

kernel-contract (project rule)
    *What*: a ``kernels/<name>/kernel.py`` without a ``ref.py`` oracle or
    a parity test referencing ``kernels.<name>``; top-K kernels hardcoding
    a ``<= -1e29`` padding sentinel instead of importing ``NEG_INF``.
    *Why*: the gateway filters selected tools by ``score > NEG_INF / 2``;
    a drifted sentinel silently surfaces padding as results, and a kernel
    without an oracle cannot be trusted after an interpreter/backend bump.
    *Fix*: add ``ref.py`` + a parity test; import
    ``repro.core.retrieval.NEG_INF``.

Suppression and baseline
========================

``# repro: noqa[rule-id]`` on the flagged line suppresses that rule there
(``# repro: noqa`` suppresses all). ``analysis_baseline.json`` (repo root)
grandfathers deliberate exceptions, content-matched so line drift does not
invalidate entries; stale entries are warned about. Regenerate with
``python -m repro.analysis --write-baseline`` (existing justifications are
kept; new entries get ``TODO: justify``).

Adding a rule: subclass ``repro.analysis.rules.Rule``, decorate with
``@register``, add a catalog entry above, and give it true-positive /
true-negative fixtures in ``tests/test_analysis.py``.
"""
from repro.analysis.engine import run, scan
from repro.analysis.findings import Baseline, Finding
from repro.analysis.rules import REGISTRY, ModuleInfo, Rule, register

__all__ = [
    "Baseline",
    "Finding",
    "ModuleInfo",
    "REGISTRY",
    "Rule",
    "register",
    "run",
    "scan",
]
