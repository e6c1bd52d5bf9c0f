"""Per-batch spans, and sampled route tracing built from them.

`SpanRecorder` is the one span primitive of the route path. `route_batch`
opens one per batch; its phases (``route.embed`` ... ``route.telemetry``)
and the index layer's steps below it (``index.snapshot`` ...
``index.wait``, ``index.ivf``) are ``with spans.span(name):`` blocks.
Each stamps `clock.perf()` at both ends. While a profiler trace is active
(checked once per batch) each span also enters a
`jax.profiler.TraceAnnotation` of the same name, so it lies on the
profiler's host plane beside the device programs it enqueued; with no
trace active none is created. At batch end the one list feeds the
gateway's `route_phase_ms{phase}` and `index_step_ms{step}` histograms and,
for a sampled batch, a `RouteTrace`. The offline fit of
`launch.serve.build_router` opens one too (``fit.refine``, ``fit.gate``,
``fit.grow``), which feeds `fit_phase_ms{phase}`.

Histograms answer "what is p99"; traces answer "where did *this* slow batch
spend it". The tracer samples ~1-in-N `route_batch` calls (seeded Bernoulli
sampler — deterministic for a given seed and call sequence, so tests and
replayed traffic produce identical trace sets) and records one `RouteTrace`
per sampled batch: phase spans (embed/cache/pad/adapter/score/rerank/
assemble with millisecond durations, then the index steps inside score),
the batch size and its power-of-two bucket, the index path that served it
(backend vs exact fallback), and the (table_version, stage_version) stamp
that fully determines the scores.

Traces live in a bounded ring (`dropped` counts evictions) and export as
JSONL — one object per line, streamable — rendered by `repro-obs`
(`repro.obs.report` / `scripts/obs_report.py`).
"""
from __future__ import annotations

import dataclasses
import json
import random
import threading
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

from repro.obs import clock

__all__ = ["RouteTrace", "TraceSampler", "RouteTracer", "SpanRecorder", "current_spans"]

_ANNOTATION = None  # jax.profiler.TraceAnnotation, imported on first use
_local = threading.local()


def _annotation():
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


class _Span:
    """One ``with`` block of a `SpanRecorder`: perf stamps `t0` and `t1`.

    Holds no reference back to its recorder (no cycle for the collector)."""

    __slots__ = ("name", "t0", "t1", "_ann")

    def __init__(self, name: str, t0: Optional[float], profiling: bool):
        self.name = name
        self.t0 = t0
        self.t1: Optional[float] = None
        self._ann = _annotation()(name) if profiling else None

    def __enter__(self) -> "_Span":
        if self._ann is not None:
            self._ann.__enter__()
        if self.t0 is None:
            self.t0 = clock.perf()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = clock.perf()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False

    @property
    def ms(self) -> float:
        return (self.t1 - self.t0) * 1e3


class _NoSpan:
    """What a disabled recorder's `span` returns: times nothing."""

    t0 = t1 = 0.0
    ms = 0.0

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NO_SPAN = _NoSpan()


class SpanRecorder:
    """The spans of one route batch, in the order they started.

    `t0` is the batch's entry stamp (`clock.perf()`, taken by the caller
    before it knows whether the batch is timed). `enabled=False` (no
    metrics, no sampled trace) makes every span a no-op unless a profiler
    trace is active. `bound()` makes the recorder the calling thread's
    `current_spans()`: that is how the index layer records into the batch
    that called it without a parameter, and adds the copies it makes
    between host and device, and their bytes, with `transfer`.
    """

    __slots__ = ("t0", "profiling", "enabled", "h2d_bytes", "d2h_bytes",
                 "h2d_copies", "d2h_copies", "_spans")

    def __init__(self, enabled: bool = True, t0: Optional[float] = None):
        self.t0 = clock.perf() if t0 is None else t0
        self.profiling = bool(_annotation().is_enabled())
        self.enabled = enabled or self.profiling
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.h2d_copies = self.d2h_copies = 0
        self._spans: List[_Span] = []

    def span(self, name: str, start: Optional[float] = None):
        """A ``with`` block timed as `name`; `start` backdates it to an
        earlier `clock.perf()` stamp (the profiler span still opens here)."""
        if not self.enabled:
            return _NO_SPAN
        span = _Span(name, start, self.profiling)
        self._spans.append(span)
        return span

    def transfer(self, h2d: int = 0, d2h: int = 0) -> None:
        """One copy of `h2d` bytes up and/or one of `d2h` bytes down."""
        if h2d:
            self.h2d_bytes += int(h2d)
            self.h2d_copies += 1
        if d2h:
            self.d2h_bytes += int(d2h)
            self.d2h_copies += 1

    def entry_wall(self) -> float:
        """The entry stamp `t0` on the wall clock (for exported records)."""
        return clock.wall() - (clock.perf() - self.t0)

    @property
    def spans(self) -> List[Tuple[str, float]]:
        """(name, ms) of every closed span, in start order."""
        return [(s.name, s.ms) for s in self._spans if s.t1 is not None]

    def under(self, prefix: str) -> List[Tuple[str, float]]:
        """`spans` whose name starts with `prefix`, named without it."""
        n = len(prefix)
        return [(s.name[n:], s.ms) for s in self._spans
                if s.t1 is not None and s.name.startswith(prefix)]

    def bound(self) -> "_Bound":
        """``with recorder.bound():`` — the thread's `current_spans()` inside."""
        return _Bound(self)


class _Bound:
    __slots__ = ("_rec", "_prev")

    def __init__(self, rec: SpanRecorder):
        self._rec = rec

    def __enter__(self) -> SpanRecorder:
        self._prev = getattr(_local, "spans", None)
        _local.spans = self._rec
        return self._rec

    def __exit__(self, *exc) -> bool:
        _local.spans = self._prev
        return False


class _NullRecorder(SpanRecorder):
    """`current_spans()` outside any batch: records nothing."""

    def __init__(self):
        self.t0 = 0.0
        self.profiling = self.enabled = False
        self.h2d_bytes = self.d2h_bytes = 0
        self.h2d_copies = self.d2h_copies = 0
        self._spans = []

    def transfer(self, h2d: int = 0, d2h: int = 0) -> None:
        pass


_NULL = _NullRecorder()


def current_spans() -> SpanRecorder:
    """The recorder bound to the calling thread, or one that records nothing."""
    return getattr(_local, "spans", None) or _NULL


@dataclasses.dataclass(frozen=True)
class RouteTrace:
    trace_id: int  # tracer-unique, in sampled order
    ts: float  # wall-clock at batch entry
    batch_size: int
    bucket: int  # pow2 bucket the batch padded into
    path: str  # "index:<backend>" | "exact" — which scorer served it
    table_version: int
    stage_version: int
    # (name, duration_ms) in start order: phases by their route_phase_ms
    # label, index steps as "index.<step>" (inside "score")
    spans: Tuple[Tuple[str, float], ...]
    total_ms: float

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["spans"] = {name: ms for name, ms in self.spans}
        return d


class TraceSampler:
    """Seeded ~1-in-N Bernoulli sampler (deterministic per seed + sequence).

    A modulo counter would sample deterministically too, but phase-locks to
    periodic traffic (every sampled batch is the same position in a
    scheduler cycle); the seeded PRNG keeps determinism without the
    aliasing. `sample_every <= 1` samples everything (tests, debugging).
    """

    def __init__(self, sample_every: int = 64, seed: int = 0):
        self.sample_every = max(int(sample_every), 1)
        self._rng = random.Random(seed)
        self._lock = threading.Lock()

    def sample(self) -> bool:
        if self.sample_every == 1:
            return True
        with self._lock:  # Random() is not thread-safe under free-threading
            return self._rng.random() < 1.0 / self.sample_every


class RouteTracer:
    """Bounded ring of sampled `RouteTrace` records + JSONL export."""

    def __init__(
        self,
        sample_every: int = 64,
        capacity: int = 1024,
        seed: int = 0,
    ):
        assert capacity >= 1
        self.sampler = TraceSampler(sample_every, seed)
        self.capacity = int(capacity)
        self._ring: Deque[RouteTrace] = deque()
        self._next_id = 0
        self.dropped = 0
        self._lock = threading.Lock()

    def sample(self) -> bool:
        """Decide at batch entry; the gateway only stamps spans when True."""
        return self.sampler.sample()

    def record(
        self,
        batch_size: int,
        bucket: int,
        path: str,
        table_version: int,
        stage_version: int,
        spans: List[Tuple[str, float]],
        total_ms: float,
        ts: Optional[float] = None,  # wall-clock at batch entry (default: now)
    ) -> RouteTrace:
        with self._lock:
            trace = RouteTrace(
                trace_id=self._next_id,
                ts=clock.wall() if ts is None else float(ts),
                batch_size=int(batch_size),
                bucket=int(bucket),
                path=path,
                table_version=int(table_version),
                stage_version=int(stage_version),
                spans=tuple((str(n), float(ms)) for n, ms in spans),
                total_ms=float(total_ms),
            )
            self._next_id += 1
            if len(self._ring) >= self.capacity:
                self._ring.popleft()
                self.dropped += 1
            self._ring.append(trace)
            return trace

    # --------------------------------------------------------------- reading
    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def traces(self) -> List[RouteTrace]:
        with self._lock:
            return list(self._ring)

    def get(self, trace_id: int) -> Optional[RouteTrace]:
        """Retained trace by id, or None (evicted / never sampled) — the
        lookup behind exemplar links ("your p99 bucket → this trace")."""
        with self._lock:
            for t in reversed(self._ring):
                if t.trace_id == trace_id:
                    return t
        return None

    def export_jsonl(self, path: str) -> int:
        """Write retained traces as JSONL; returns the number written."""
        traces = self.traces()
        with open(path, "w") as f:
            for t in traces:
                f.write(json.dumps(t.as_dict()) + "\n")
        return len(traces)

    def phase_summaries(self) -> Dict[str, dict]:
        """Per-phase {count, mean, p50, p99} over the retained traces —
        the exact-sample view (`repro.obs.summary.percentile_stats`) the
        `repro-obs` report renders."""
        from repro.obs.summary import percentile_stats

        by_phase: Dict[str, List[float]] = {}
        for t in self.traces():
            for name, ms in t.spans:
                by_phase.setdefault(name, []).append(ms)
        return {
            name: percentile_stats(samples).as_dict()
            for name, samples in sorted(by_phase.items())
        }
