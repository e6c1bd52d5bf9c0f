"""ToolIndexManager: version-tracked index lifecycle between the database
and the scorer backends.

The swap-compatibility problem this layer solves: an index (IVF clusters,
a device-resident table copy, Pallas tiles) is derived state over one table
snapshot, but `ToolsDatabase.swap_table`/`rollback` can land at any moment
— including mid-batch, including from the PR 2 control plane's guard. The
manager keeps the invariant that *served scores always come from the table
version they are reported under*:

  * every `topk` call starts from an atomic `db.snapshot()`;
  * if the built backend matches the snapshot version (and can honor the
    batch's candidate mask), it serves;
  * otherwise the call is served by the exact dense fallback **on the
    snapshot itself** — the PR 1 jitted `topk_dense` path with a
    version-keyed device cache, numerically identical to `DenseBackend` —
    and an async rebuild for the new version is kicked off (at most one
    in-flight build per version).

Rebuilds are also triggered eagerly: the manager registers a
`ToolsDatabase.add_swap_listener` hook at construction, so a control-plane
swap or guard rollback starts the rebuild immediately instead of on the
next unlucky request. `async_rebuild=False` makes builds synchronous (the
swap listener blocks until the index is fresh) — deterministic for tests
and offline jobs; serving processes keep the default. Backends whose build
is one device upload (`build_is_cheap`: dense, pallas) always rebuild
inline — under swap churn a rebuild thread per version costs more than the
build itself and doubles the uploads; only genuinely expensive builds
(IVF k-means) go to a background thread.

A failed build (bad table, backend bug) is counted in
`stats["build_failures"]` and leaves the fallback serving — an index is an
optimization, never a correctness dependency.

Each `topk` call's snapshot, backend lookup and version check is the
`index.snapshot` span of the calling batch (`repro.obs.trace`); the
backend's own round trip times the rest. The table each build uploads
(`upload_bytes` of a device backend) counts into
`index_transfer_bytes_total{dir=h2d}`, and as one copy into
`index_transfers_total{dir=h2d}`, so a swap's upload shows there.
"""
from __future__ import annotations

import threading
import time  # time.sleep only; clocks come from repro.obs.clock
from typing import Dict, Optional, Tuple, Union

import numpy as np

from repro.index.dense import DenseBackend
from repro.obs import clock
from repro.obs.metrics import MetricsRegistry, get_registry
from repro.obs.trace import current_spans
from repro.router.tooldb import ToolsDatabase

__all__ = ["ToolIndexManager"]


class _IndexInstruments:
    """Preresolved metric handles (catalog: `repro.obs` docstring)."""

    def __init__(self, registry: MetricsRegistry):
        self.registry = registry
        self.served = {
            "index": registry.counter("index_served_total", path="index"),
            "exact": registry.counter("index_served_total", path="exact"),
        }
        self.rebuilds = registry.counter("index_rebuilds_total")
        self.build_failures = registry.counter("index_build_failures_total")
        self.build_ms = registry.histogram("index_build_ms")
        self.h2d_bytes = registry.counter("index_transfer_bytes_total", dir="h2d")
        self.h2d_copies = registry.counter("index_transfers_total", dir="h2d")

    def uploaded(self, backend) -> None:
        """Count the table a device backend uploaded when it was built."""
        nbytes = getattr(backend, "upload_bytes", 0)
        if nbytes:
            self.h2d_bytes.inc(nbytes)
            self.h2d_copies.inc()


def _build_backend(kind: str, table: np.ndarray, table_version: int, **opts):
    # local import so manager <-> package __init__ stay cycle-free
    from repro.index import build_backend

    return build_backend(kind, table, table_version, **opts)


class ToolIndexManager:
    def __init__(
        self,
        db: ToolsDatabase,
        backend: str = "dense",
        backend_opts: Optional[dict] = None,
        async_rebuild: bool = True,
        watch_swaps: bool = True,
        metrics: Union[MetricsRegistry, bool, None] = None,
        bus: Optional["EventBus"] = None,  # repro.obs.events
    ):
        from repro.index import BACKENDS  # call-time import: no module cycle

        if backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {backend!r} (available: {sorted(BACKENDS)})"
            )
        self.db = db
        self.backend_kind = backend
        self.backend_opts = dict(backend_opts or {})
        # cheap builds (dense/pallas: one device upload) always run inline —
        # a rebuild thread per swap costs more than the build and doubles
        # uploads (listener build + fallback cache) under swap churn
        self._inline_build = bool(
            getattr(BACKENDS[backend], "build_is_cheap", False)
        )
        self.async_rebuild = async_rebuild and not self._inline_build
        self._lock = threading.Lock()
        # waiters for an in-flight build (refresh(block=True) must join the
        # running build, not duplicate a 10+ s k-means); shares self._lock
        self._build_cond = threading.Condition(self._lock)
        self._backend = None
        self._building_for: Optional[int] = None  # version with an in-flight build
        self._failed_for: Optional[int] = None  # version whose build failed
        self._fallback: Optional[DenseBackend] = None  # exact path, per version
        self.stats: Dict[str, int] = {
            "served_index": 0,
            "served_exact": 0,
            "rebuilds": 0,
            "build_failures": 0,
        }
        # telemetry mirrors of `stats` + rebuild lifecycle events; the bus
        # is a plain attribute so launchers can attach one to a manager a
        # router already built (`manager.bus = bus`)
        if metrics is False:
            self._obs: Optional[_IndexInstruments] = None
        else:
            registry = metrics if isinstance(metrics, MetricsRegistry) else get_registry()
            self._obs = _IndexInstruments(registry)
        self.bus = bus
        # which path served the calling thread's last topk ("index:<kind>" |
        # "exact"): thread-local so concurrent batches don't cross-stamp
        # their traces during a fallback-serving window
        self._tls = threading.local()
        # fail fast on misconfigured backend_opts: a tiny synchronous
        # validation build surfaces TypeError/ValueError at construction
        # instead of a silent build-failure loop behind the fallback
        _, probe_table = db.snapshot()
        probe = _build_backend(
            backend, np.asarray(probe_table[:64]), -1, **self.backend_opts
        )
        if self._obs is not None:
            self._obs.uploaded(probe)
        self._watching = watch_swaps
        if watch_swaps:
            db.add_swap_listener(self._on_swap)
        self.refresh(block=not self.async_rebuild)

    # ------------------------------------------------------------- lifecycle
    def _on_swap(self, new_version: int) -> None:
        self.refresh(block=not self.async_rebuild)

    def close(self) -> None:
        """Unregister from the database's swap listeners (idempotent).

        A manager that is being retired (router torn down, backend
        reconfigured) must be closed, or the database keeps a strong
        reference and keeps triggering rebuilds — and keeps this manager's
        table copies alive — on every future swap.
        """
        if self._watching:
            self.db.remove_swap_listener(self._on_swap)
            self._watching = False

    def is_fresh(self) -> bool:
        """True when the built index matches the database's live version."""
        with self._lock:
            backend = self._backend
        return backend is not None and backend.table_version == self.db.table_version

    def wait_ready(self, timeout_s: float = 60.0, poll_s: float = 0.01) -> bool:
        """Block until the index is fresh (benchmarks/tests); True on success.

        Returns False immediately (not after the full timeout) when the
        build for the live version has already failed and nothing is
        retrying it — callers must check the result: False means the exact
        fallback is serving, not the configured backend.
        """
        deadline = clock.monotonic() + timeout_s
        while clock.monotonic() < deadline:
            if self.is_fresh():
                return True
            with self._lock:
                building = self._building_for is not None
                failed_version = self._failed_for
            if not building and failed_version == self.db.table_version:
                return False  # doomed: failed build, no retry in flight
            time.sleep(poll_s)
        return self.is_fresh()

    def refresh(self, block: bool = False) -> None:
        """Ensure a build for the current table version is done or in flight."""
        version, table = self.db.snapshot()
        with self._lock:
            if self._backend is not None and self._backend.table_version == version:
                return
            if self._building_for == version:
                if not block:
                    return  # one in-flight build per version is enough
                # join the in-flight build instead of duplicating it; when
                # it finishes (installed or failed) this refresh is done
                while self._building_for == version:
                    self._build_cond.wait()
                return
            if self._failed_for == version and not block:
                # this version's build already failed (counted in stats);
                # don't respawn a doomed build per serving call — the next
                # swap, or an explicit refresh(block=True), retries
                return
            self._building_for = version
        if block:
            self._build(version, np.asarray(table))
        else:
            threading.Thread(
                target=self._build,
                args=(version, np.asarray(table)),
                name=f"index-rebuild-v{version}",
                daemon=True,
            ).start()

    def _build(self, version: int, table: np.ndarray) -> None:
        bus, obs = self.bus, self._obs
        if bus is not None:
            bus.publish("rebuild_start", plane="index", version=version,
                        backend=self.backend_kind)
        t0 = clock.perf()
        opts = dict(self.backend_opts)
        with self._lock:
            prev = self._backend
        if prev is not None and hasattr(prev, "warm_start_state"):
            # swap-triggered rebuild: seed the new build from the outgoing
            # index's state (IVF k-means centroids). Control-plane swaps
            # move the table gently, so the warm start converges in a
            # fraction of the iterations; a stale/incompatible state is
            # validated and ignored by the backend, never an error.
            opts["warm_start"] = prev.warm_start_state()
        try:
            backend = _build_backend(self.backend_kind, table, version, **opts)
        except Exception as exc:
            with self._lock:
                self.stats["build_failures"] += 1
                self._failed_for = version
                if self._building_for == version:
                    self._building_for = None
                self._build_cond.notify_all()
            if obs is not None:
                obs.build_failures.inc()
            if bus is not None:
                bus.publish("rebuild_failure", plane="index", version=version,
                            backend=self.backend_kind, error=repr(exc))
            return  # the exact fallback keeps serving
        build_ms = clock.duration_ms(t0)
        with self._lock:
            # never replace a fresher index with a slower build's older one
            if self._backend is None or self._backend.table_version <= version:
                self._backend = backend
                self.stats["rebuilds"] += 1
            if self._building_for == version:
                self._building_for = None
            self._build_cond.notify_all()
        if obs is not None:
            obs.rebuilds.inc()
            obs.build_ms.record(build_ms)
            obs.uploaded(backend)
        if bus is not None:
            bus.publish("rebuild_finish", plane="index", version=version,
                        backend=self.backend_kind, build_ms=build_ms)

    # ----------------------------------------------------------------- serve
    def topk(
        self,
        queries: np.ndarray,
        k: int,
        candidate_mask: Optional[np.ndarray] = None,
    ) -> Tuple[np.ndarray, np.ndarray, int]:
        """(scores [Q, k], indices [Q, k], table_version) for this batch.

        The returned version is the snapshot the scores were computed from —
        the backend's when it serves, the fallback snapshot's otherwise.
        """
        with current_spans().span("index.snapshot"):
            version, table = self.db.snapshot()
            with self._lock:
                backend = self._backend
            stale = backend is None or backend.table_version != version
        if stale:
            # cheap builds (a device upload) run inline — the PR 1 serving
            # path paid exactly this upload on version change; expensive
            # builds (IVF) go async and this batch serves the exact fallback
            self.refresh(block=self._inline_build)
            with self._lock:
                backend = self._backend
        maskable = candidate_mask is None or (
            backend is not None and backend.supports_masks
        )
        if backend is not None and backend.table_version == version and maskable:
            scores, idx = backend.topk(queries, k, candidate_mask)
            with self._lock:  # counters race under concurrent serving
                self.stats["served_index"] += 1
            self._tls.path = f"index:{self.backend_kind}"
            if self._obs is not None:
                self._obs.served["index"].inc()
            return scores, idx, version
        scores, idx = self._exact_topk(queries, table, version, k, candidate_mask)
        with self._lock:
            self.stats["served_exact"] += 1
        self._tls.path = "exact"
        if self._obs is not None:
            self._obs.served["exact"].inc()
        return scores, idx, version

    def last_path(self) -> str:
        """Which path served the calling thread's most recent `topk`."""
        return getattr(self._tls, "path", "unknown")

    def _exact_topk(
        self,
        queries: np.ndarray,
        table: np.ndarray,
        version: int,
        k: int,
        candidate_mask: Optional[np.ndarray],
    ) -> Tuple[np.ndarray, np.ndarray]:
        # the exact path IS a DenseBackend over the snapshot — one
        # implementation, so fallback and dense-index numerics are identical
        # by construction; rebuilt only on version change (a benign race can
        # at worst double-upload, exactly like the PR 1 gateway cache)
        fallback = self._fallback
        if fallback is None or fallback.table_version != version:
            fallback = DenseBackend(table, version)
            self._fallback = fallback
            if self._obs is not None:
                self._obs.uploaded(fallback)
        return fallback.topk(queries, k, candidate_mask)
