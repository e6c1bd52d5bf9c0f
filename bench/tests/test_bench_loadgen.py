"""The open loop's arithmetic: arrivals, queries, latency from the due time."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import loadgen  # noqa: E402


class FakeClock:
    """Time moves only when the client sleeps or the served call takes time."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 1e-7  # each read costs a little, so a spin ends
        return self.t

    def sleep(self, d):
        self.t += d


def make_route(clock, service_s, stall_at=None, stall_s=0.0):
    calls = []

    def route(queries):
        calls.append(len(queries))
        clock.t += service_s + (stall_s if len(calls) == stall_at else 0.0)
        return [("ok", q) for q in queries]

    return route, calls


def test_arrivals_fixed_count_and_gaps_in_a_seeded_order():
    a = loadgen.arrival_times(1000.0, 2.0, seed=3_000_000_007)
    b = loadgen.arrival_times(1000.0, 2.0, seed=12)
    assert len(a) == len(b) == 2000
    assert a[0] == 0.0 and np.all(np.diff(a) > 0) and a[-1] < 2.0
    # the same set of gaps, another order
    ga, gb = np.diff(np.append(a, 2.0)), np.diff(np.append(b, 2.0))
    np.testing.assert_allclose(np.sort(ga), np.sort(gb), rtol=1e-9, atol=1e-12)
    assert not np.allclose(ga, gb)
    # exponential: the gaps' spread is a Poisson process's (cv ~ 1)
    assert 0.9 < ga.std() / ga.mean() < 1.1
    np.testing.assert_array_equal(a, loadgen.arrival_times(1000.0, 2.0, seed=3_000_000_007))


def test_pool_draws_seeded_uniform_and_in_range():
    a = loadgen.pool_draws(600, 60_000, seed=2**33 + 5)
    np.testing.assert_array_equal(a, loadgen.pool_draws(600, 60_000, seed=2**33 + 5))
    assert a.min() >= 0 and a.max() < 600
    counts = np.bincount(a, minlength=600)
    assert counts.min() > 50 and counts.max() < 150  # ~100 each, no skew
    assert not np.array_equal(a, loadgen.pool_draws(600, 60_000, seed=2**33 + 6))


def test_arrivals_and_queries_are_separate_streams():
    """The same seed draws arrivals and queries from streams of their own."""
    due = loadgen.arrival_times(1000.0, 1.0, seed=99)
    draws = loadgen.pool_draws(600, len(due), seed=99)
    np.testing.assert_array_equal(due, loadgen.arrival_times(1000.0, 1.0, seed=99))
    assert not np.array_equal(draws, loadgen.pool_draws(600, len(due), seed=98))


def test_latency_is_counted_from_the_due_time():
    clock = FakeClock()
    route, calls = make_route(clock, service_s=1e-3)
    due = np.array([0.0, 0.0001, 0.0002, 0.010, 0.020])
    win = loadgen.drive(route, list(range(5)), due, max_batch=64, seconds=1.0,
                        clock=clock, sleep=clock.sleep)
    assert calls[0] == 1  # the first request is handed alone, the next two wait for it
    lat = (win.done - win.due) * 1e3
    assert lat[0] == pytest.approx(1.0, abs=0.01)
    # due while the first call ran: queued, then served together
    assert lat[1] == pytest.approx(2.0 - 0.1, abs=0.01)
    assert lat[2] == pytest.approx(2.0 - 0.2, abs=0.01)
    assert win.backlog == 0 and len(win.results) == 5
    s = loadgen.summarize(win)
    assert s.completed == 5 and s.mean_batch == pytest.approx(5 / 4)


def test_a_stall_inside_the_window_moves_p99():
    due = loadgen.arrival_times(2000.0, 1.0, seed=7)
    p99 = {}
    for stall in (0.0, 0.05):
        clock = FakeClock()
        route, _ = make_route(clock, service_s=2e-4, stall_at=100, stall_s=stall)
        win = loadgen.drive(route, list(range(len(due))), due, 64, 1.0,
                            clock=clock, sleep=clock.sleep)
        s = loadgen.summarize(win)
        assert s.completed == len(due) == 2000
        p99[stall] = loadgen.percentile(s.latency_ms, 99)
    # a 50 ms stall delays every request due behind it (~100 of 2000 routes)
    assert p99[0.0] < 1.0 < 20.0 < p99[0.05]


def test_overload_leaves_backlog_and_rate_is_capacity():
    clock = FakeClock()
    route, calls = make_route(clock, service_s=64 / 1000.0)  # 1000 routes/s at full batch
    due = loadgen.arrival_times(3000.0, 2.0, seed=1)
    win = loadgen.drive(route, list(range(len(due))), due, 64, 2.0, clock=clock, sleep=clock.sleep)
    s = loadgen.summarize(win)
    assert max(calls) == 64 and s.routes_per_s == pytest.approx(1000.0, rel=0.05)
    assert win.backlog == pytest.approx(4000, rel=0.05)


def test_percentile_is_nearest_rank_over_all_samples():
    v = np.arange(1, 1001, dtype=float)
    assert loadgen.percentile(v, 50) == 500.0
    assert loadgen.percentile(v, 99) == 990.0
    assert loadgen.beyond(1000, 99) == 10
    assert np.isnan(loadgen.percentile([], 50))


def test_failed_calls_leave_no_result():
    clock = FakeClock()

    def route(queries):
        clock.t += 1e-3
        raise RuntimeError("device lost")

    win = loadgen.drive(route, list(range(3)), np.array([0.0, 0.01, 0.02]), 64, 1.0,
                        clock=clock, sleep=clock.sleep)
    assert win.results == [None] * 3 and len(win.errors) == 3
    assert loadgen.summarize(win).completed == 0
