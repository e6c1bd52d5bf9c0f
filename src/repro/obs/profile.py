"""Continuous profiling: live compile/cost telemetry for the hot path.

PR 5's analysis pass checks the repo's compile discipline *offline*: the
retrace CI leg fails a build whose hot jits trace beyond the power-of-two
bucket set, and the jit-lint rules catch construction-time hazards. None of
that sees a *production* retrace — a novel batch shape, a silently changed
dtype, a stage promotion that invalidates a cache — which lands as a
multi-ms stall against the 10 ms p99 budget with no metric to alert on.
This module turns those invariants into live telemetry:

* `JitProfiler` — polls each tracked jitted callable's compile-cache size
  (`fn._cache_size()`, the same private-but-stable probe
  `analysis/retrace.py` uses). The **first** `collect()` establishes a
  baseline so warmup compiles are not counted as incidents; after that,
  every cache growth increments ``jit_compiles_total{fn=...}`` and the
  absolute size is mirrored to ``jit_cache_size{fn=...}``. With the
  counters in the registry, the `TimeSeriesRing` windows them like any
  other signal and `default_slos()`'s ``jit_retrace_rate`` SLO alerts on a
  sustained post-warmup compile rate — an in-production retrace is now an
  alertable event, not a CI-only invariant.

* Cost stamping — `stamp_cost(name, *args)` lowers + compiles the tracked
  jit against representative arguments and records XLA's
  ``cost_analysis()`` FLOPs / bytes-accessed for that program
  (`stamp_router_costs` derives representative shapes from a live router).
  Lowering is out-of-band of the jit call cache — it never grows
  `_cache_size` — so stamping cannot show up as a retrace. The result is
  exported at ``/profile``: per-program static cost next to per-program
  compile activity.

* `SamplingProfiler` — an opt-in wall-clock sampler for the controller
  daemons: a daemon thread snapshots ``sys._current_frames()`` at a fixed
  interval, filters to the registered thread idents, and aggregates
  collapsed stacks into counts. Self-time is attributed to whatever frame
  is on top when the sample lands — the classic statistical profile, at
  ~zero cost to the profiled threads (no tracing hook is installed). Off
  by default; `launch/serve.py` enables it behind ``--profile-daemons``.
"""
from __future__ import annotations

import sys
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis.retrace import supports_cache_size
from repro.common.meshctx import cost_analysis_dict

__all__ = ["JitProfiler", "SamplingProfiler", "stamp_router_costs"]


def _cost_summary(compiled) -> dict:
    """XLA's cost analysis as {"flops": float, "bytes_accessed": float}
    (keys absent where the backend reports none)."""
    ca = cost_analysis_dict(compiled)
    out = {}
    if "flops" in ca:
        out["flops"] = float(ca["flops"])
    if "bytes accessed" in ca:
        out["bytes_accessed"] = float(ca["bytes accessed"])
    return out


class JitProfiler:
    """Compile-cache poller + cost stamper over named jitted callables.

    `collect()` is cheap (one attribute read per fn) and is meant to run on
    the `TimeSeriesRing` tick cadence; the first call only baselines.
    """

    def __init__(
        self,
        jits: Optional[Dict[str, Callable]] = None,
        registry=None,  # repro.obs.metrics.MetricsRegistry
    ):
        if jits is None:
            from repro.router.gateway import hot_path_jits

            jits = hot_path_jits()
        self._fns: Dict[str, Callable] = {}
        self.unsupported: List[str] = []
        for name, fn in jits.items():
            if supports_cache_size(fn):
                self._fns[name] = fn
            else:
                self.unsupported.append(name)
        self.registry = registry
        # last observed cache size per fn; None until the baseline collect
        self._last: Dict[str, Optional[int]] = {n: None for n in self._fns}
        self._compiles: Dict[str, int] = {n: 0 for n in self._fns}
        self._costs: Dict[str, dict] = {}
        self._lock = threading.Lock()
        self._counters = self._gauges = None
        if registry is not None:
            self._counters = {
                n: registry.counter("jit_compiles_total", fn=n) for n in self._fns
            }
            self._gauges = {
                n: registry.gauge("jit_cache_size", fn=n) for n in self._fns
            }

    def names(self) -> List[str]:
        return sorted(self._fns)

    # ------------------------------------------------------------- collecting
    def collect(self) -> Dict[str, int]:
        """Poll every cache size; count post-baseline growth as compiles.

        Returns {fn: cache_size}. The first call per fn records the
        baseline without incrementing — warmup compiles are expected, only
        growth *after* the profiler is watching is a retrace signal.
        """
        sizes = {n: int(f._cache_size()) for n, f in self._fns.items()}
        with self._lock:
            for n, size in sizes.items():
                last = self._last[n]
                if last is not None and size > last:
                    delta = size - last
                    self._compiles[n] += delta
                    if self._counters is not None:
                        self._counters[n].inc(delta)
                self._last[n] = size
                if self._gauges is not None:
                    self._gauges[n].set(size)
        return sizes

    # --------------------------------------------------------------- stamping
    def stamp_cost(self, name: str, *args, **kwargs) -> dict:
        """Lower + compile `name` against `args` and record FLOPs/bytes.

        Lowering is out-of-band of the jit call cache — it does not grow
        `_cache_size` (asserted in the tests) — so stamping never
        manufactures the retrace signal it exists to watch for.
        """
        fn = self._fns[name]
        cost = _cost_summary(fn.lower(*args, **kwargs).compile())
        cost["arg_shapes"] = [
            list(np.shape(a)) for a in args if hasattr(a, "shape")
        ]
        with self._lock:
            self._costs[name] = cost
        return cost

    # ---------------------------------------------------------------- reading
    def snapshot(self) -> dict:
        """The ``/profile`` payload: per-jit cache/compile/cost state."""
        with self._lock:
            jits = {
                n: {
                    "cache_size": self._last[n] if self._last[n] is not None else 0,
                    "compiles_total": self._compiles[n],
                    "baselined": self._last[n] is not None,
                    "cost": self._costs.get(n),
                }
                for n in self._fns
            }
        return {"jits": jits, "unsupported": list(self.unsupported)}


def stamp_router_costs(
    profiler: JitProfiler, router, batch_size: int = 1
) -> Dict[str, dict]:
    """Stamp the profiler's hot jits with shapes a live `router` serves.

    Derives one representative program per active entry point — the scoring
    path always, the adapter/reranker only when their stages are live (an
    inactive stage has no compiled program to cost). Batch size is padded to
    the same power-of-two bucket `route_batch` would use, so the stamped
    program IS the serving program.
    """
    import jax.numpy as jnp

    from repro.common.bucketing import pad_amount

    q = int(batch_size)
    q_pad = q + pad_amount(q)
    _, emb = router.db.snapshot()
    emb = np.asarray(emb)
    n_t = emb.shape[0]
    qblock = jnp.asarray(emb[:1].repeat(q_pad, axis=0))
    stamped: Dict[str, dict] = {}
    _, stages = router.stage_set()
    rerank = stages.has_reranker
    c = (
        min(router.k * router.candidate_multiplier, n_t)
        if rerank
        else min(router.k, n_t)
    )
    if "topk_dense" in profiler.names():
        stamped["topk_dense"] = profiler.stamp_cost(
            "topk_dense", qblock, jnp.asarray(emb), c
        )
    if "adapter_apply" in profiler.names() and stages.has_adapter:
        stamped["adapter_apply"] = profiler.stamp_cost(
            "adapter_apply", stages.adapter_params, qblock,
            scale=stages.adapter_scale,
        )
    if "rerank_topk_scored" in profiler.names() and rerank:
        from repro.core.features import N_FEATURES

        feats = jnp.zeros((q_pad, c, N_FEATURES), jnp.float32)
        cand = jnp.zeros((q_pad, c), jnp.int32)
        stamped["rerank_topk_scored"] = profiler.stamp_cost(
            "rerank_topk_scored", stages.mlp_params, feats, cand, router.k
        )
    return stamped


class SamplingProfiler:
    """Opt-in statistical wall-clock profiler over chosen threads.

    Samples `sys._current_frames()` on a daemon thread and aggregates
    collapsed call stacks (outermost;...;innermost) per registered thread.
    The profiled threads pay nothing — no trace hook, no instrumentation —
    and the profile's resolution is the sampling interval.
    """

    def __init__(self, interval_s: float = 0.05, max_depth: int = 24):
        self.interval_s = float(interval_s)
        self.max_depth = int(max_depth)
        self._targets: Dict[int, str] = {}  # thread ident -> display name
        self._samples: Dict[str, Dict[str, int]] = {}  # name -> stack -> n
        self._n_ticks = 0
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self.last_loop_error: Optional[str] = None

    def watch_thread(self, thread: threading.Thread, name: Optional[str] = None):
        """Register a (started) thread for sampling."""
        assert thread.ident is not None, "watch_thread needs a started thread"
        with self._lock:
            self._targets[thread.ident] = name or thread.name
        return self

    def sample_once(self) -> int:
        """Take one sample of every watched thread; returns threads seen."""
        frames = sys._current_frames()
        seen = 0
        with self._lock:
            targets = dict(self._targets)
        collapsed: List[Tuple[str, str]] = []
        for ident, name in targets.items():
            frame = frames.get(ident)
            if frame is None:
                continue  # thread exited; keep the accumulated profile
            stack: List[str] = []
            depth = 0
            while frame is not None and depth < self.max_depth:
                code = frame.f_code
                stack.append(f"{code.co_name}@{code.co_filename.rsplit('/', 1)[-1]}")
                frame = frame.f_back
                depth += 1
            collapsed.append((name, ";".join(reversed(stack))))
            seen += 1
        with self._lock:
            self._n_ticks += 1
            for name, stack in collapsed:
                per = self._samples.setdefault(name, {})
                per[stack] = per.get(stack, 0) + 1
        return seen

    def start(self) -> "SamplingProfiler":
        assert self._thread is None, "sampling profiler already running"
        self._stop.clear()

        def _loop():
            while not self._stop.is_set():
                try:
                    self.sample_once()
                    self.last_loop_error = None
                except Exception as exc:  # noqa: BLE001 — daemon must survive
                    self.last_loop_error = f"{type(exc).__name__}: {exc}"
                self._stop.wait(self.interval_s)

        self._thread = threading.Thread(
            target=_loop, name="sampling-profiler", daemon=True
        )
        self._thread.start()
        return self

    def stop(self, timeout_s: float = 5.0) -> None:
        """Idempotent; joins the sampler with a bounded wait."""
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout=timeout_s)
        self._thread = None

    def snapshot(self, top: int = 10) -> dict:
        """Per-thread top collapsed stacks by sample count."""
        with self._lock:
            n_ticks = self._n_ticks
            threads = {
                name: sorted(per.items(), key=lambda kv: -kv[1])[:top]
                for name, per in self._samples.items()
            }
        return {
            "interval_s": self.interval_s,
            "n_samples": n_ticks,
            "threads": {
                name: [{"stack": s, "samples": n} for s, n in stacks]
                for name, stacks in threads.items()
            },
        }
