"""Traffic and the open-loop client.

Arrivals: `n = rate x seconds` inter-arrival gaps taken at the midpoints of
n equal-probability strata of the exponential distribution, scaled to span
the window exactly, and put in an order drawn from the seed. Every seed then
offers the same number of requests and the same set of gaps (a Poisson
process's spread of gaps) in another order, so two seeds differ in which
bursts come when, not in how much work there is.

Queries: each request asks one query of the configuration's pool, drawn
uniformly from the seed. With the route cache off a route's work depends on
its query's length alone, so no skew or paraphrase would change what is
measured; the pool's own lengths (7-17 tokens) are the mix.

The client is one thread. When requests are due it hands every due request,
up to the cell's batch cap, to one `route` call; when none is due it waits
for the next arrival. Each route is timed from when it was due to when the
call that served it returned, so a stall delays every request behind it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import time
from typing import Callable, List, Optional, Sequence

import numpy as np

# sub-streams of one seed: arrivals and queries never share draws
_ARRIVALS, _QUERIES = 1, 2


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def arrival_times(rate_per_s: float, seconds: float, seed: int) -> np.ndarray:
    """Due offsets (s) of `round(rate x seconds)` requests over [0, seconds)."""
    n = max(1, int(round(rate_per_s * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)  # exponential quantiles, mean ~1
    gaps = rng_for(seed, _ARRIVALS).permutation(gaps)
    gaps *= seconds / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def pool_draws(pool_size: int, n: int, seed: int) -> np.ndarray:
    """The pool index of each of `n` requests, uniform over the pool."""
    return rng_for(seed, _QUERIES).integers(0, pool_size, size=n)


@dataclasses.dataclass
class Window:
    """What the open-loop client saw. Times are seconds from the first due arrival."""

    offered: int  # requests due in [0, seconds)
    due: np.ndarray  # [h] due times of the handed requests
    handed: np.ndarray  # [h] when each was handed to `route`
    done: np.ndarray  # [h] when the call that served it returned
    batch_of: np.ndarray  # [h] index of the call that served it
    results: list  # [h] what `route` returned for it (None if it raised or fell short)
    errors: List[str]  # one entry per call that raised
    elapsed: float  # first due arrival to the last completion
    wake_late: np.ndarray  # how late the client woke after each idle wait

    @property
    def backlog(self) -> int:
        """Requests due inside the window that were never handed."""
        return self.offered - len(self.due)

    @property
    def n_batches(self) -> int:
        return int(self.batch_of[-1]) + 1 if len(self.batch_of) else 0


def _wait_until(target: float, clock: Callable[[], float],
                sleep: Callable[[float], None]) -> float:
    """Sleep, then spin the last ~1.5 ms; returns how late it woke (s)."""
    while True:
        rem = target - clock()
        if rem <= 0:
            return -rem
        if rem > 2e-3:
            sleep(rem - 1.5e-3)


def drive(
    route: Callable[[list], list],
    queries: Sequence[np.ndarray],
    due: np.ndarray,
    max_batch: int,
    seconds: float,
    clock: Callable[[], float] = time.perf_counter,
    sleep: Callable[[float], None] = time.sleep,
) -> Window:
    """Offer `queries` at `due` (s) to `route` for `seconds`, open loop."""
    n = len(due)
    handed = np.zeros(n)
    done = np.zeros(n)
    batch_of = np.zeros(n, np.int64)
    results: list = [None] * n
    errors: List[str] = []
    wake = []
    t0 = clock()
    i = b = 0
    while i < n:
        now = clock() - t0
        if now >= seconds:
            break
        if due[i] > now:
            wake.append(_wait_until(t0 + due[i], clock, sleep))
            continue
        j = min(i + max_batch, int(np.searchsorted(due, now, side="right")))
        handed[i:j] = now
        try:
            out = route(list(queries[i:j]))
        except Exception as exc:  # noqa: BLE001 — counted as failed routes
            errors.append(repr(exc))
            out = []
        done[i:j] = clock() - t0
        batch_of[i:j] = b
        for m, res in enumerate(out[: j - i]):
            results[i + m] = res
        i, b = j, b + 1
    elapsed = float(done[i - 1]) if i else 0.0
    return Window(
        offered=n, due=due[:i].copy(), handed=handed[:i], done=done[:i],
        batch_of=batch_of[:i], results=results[:i], errors=errors,
        elapsed=elapsed, wake_late=np.asarray(wake),
    )


@contextlib.contextmanager
def gc_paused():
    """Pause the cyclic garbage collector, what set-up left frozen first.

    The client keeps every result of the window for the check, and a full
    collection over those objects stalls the loop for ~100 ms at random: a
    cost of the benchmark's bookkeeping, not of the router. Enter it before
    the window starts; the collection it makes first is set-up.
    """
    gc.collect()
    gc.freeze()
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()
        gc.unfreeze()


def slow_calls(win: Window, factor: float = 10.0) -> List[tuple]:
    """(handed at s, service ms) of each call that took over `factor` x the median."""
    if not len(win.batch_of):
        return []
    first = np.unique(win.batch_of, return_index=True)[1]
    svc = (win.done - win.handed)[first]
    cut = factor * float(np.median(svc))
    return [(float(win.handed[i]), float(s * 1e3)) for i, s in zip(first, svc) if s > cut]


def percentile(values: np.ndarray, p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least p% at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return math.nan
    return float(v[max(0, math.ceil(p / 100.0 * len(v)) - 1)])


def beyond(n: int, p: float) -> int:
    """How many of n samples lie above the nearest-rank p-th percentile."""
    return n - max(1, math.ceil(p / 100.0 * n)) if n else 0


@dataclasses.dataclass
class Summary:
    completed: int
    routes_per_s: float
    latency_ms: np.ndarray  # per completed route, from its due time
    wait_ms: np.ndarray  # due -> handed
    service_ms: np.ndarray  # handed -> returned
    mean_batch: float

    def line(self, offered: int, backlog: int) -> str:
        lat, wait, svc = self.latency_ms, self.wait_ms, self.service_ms
        n = len(lat)
        slowest = float(svc.max()) if len(svc) else math.nan
        return (
            f"window: offered {offered}, completed {self.completed}, backlog {backlog}, "
            f"routes/s {self.routes_per_s!r}, mean batch {self.mean_batch!r}; "
            f"latency p50 {percentile(lat, 50)!r} ms ({beyond(n, 50)} routes beyond), "
            f"p90 {percentile(lat, 90)!r} ms ({beyond(n, 90)} beyond), "
            f"p99 {percentile(lat, 99)!r} ms ({beyond(n, 99)} beyond), "
            f"p99.9 {percentile(lat, 99.9)!r} ms ({beyond(n, 99.9)} beyond) of {n}; "
            f"queue wait p50 {percentile(wait, 50)!r} p99 {percentile(wait, 99)!r} ms; "
            f"service p50 {percentile(svc, 50)!r} p99 {percentile(svc, 99)!r} "
            f"max {slowest!r} ms"
        )


def summarize(win: Window, ok: Optional[np.ndarray] = None) -> Summary:
    """Latency over every route that completed (`ok`: served well-formed)."""
    if ok is None:
        ok = np.array([r is not None for r in win.results], bool)
    lat = (win.done - win.due)[ok] * 1e3
    completed = int(ok.sum())
    return Summary(
        completed=completed,
        routes_per_s=completed / win.elapsed if win.elapsed > 0 else 0.0,
        latency_ms=lat,
        wait_ms=(win.handed - win.due)[ok] * 1e3,
        service_ms=(win.done - win.handed)[ok] * 1e3,
        mean_batch=len(win.due) / win.n_batches if win.n_batches else 0.0,
    )
