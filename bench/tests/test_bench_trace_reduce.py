"""The reduction from a profiler trace to busy time, span device time and idle gaps."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce as tr  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "data" / "route_window.xplane.pb"

SPANS = [
    (0, 1010, "window"),
    (100, 400, "route_batch"), (110, 200, "embed_batch_fn"), (210, 390, "index.topk"),
    (600, 900, "route_batch"), (610, 700, "embed_batch_fn"), (710, 890, "index.topk"),
]


def synthetic():
    # the device clock runs 5,000 ns ahead of the host's: only run ids place executions
    c = tr.Module(5155, 5160, "jit_embed(1)", host=150, ops=[(5155, 5160, "%fusion = f32[4]")])
    a = tr.Module(5260, 5300, "jit_topk_dense(7)", host=250, ops=[
        (5260, 5270, "%fusion = f32[64,9]"),
        (5275, 5300, '%custom-call = (f32[64,5]) custom-call(), custom_call_target="TopK"')])
    b = tr.Module(5760, 5800, "jit_topk_dense(7)", host=750,
                  ops=[(5760, 5800, "%fusion = f32[64,9]")])
    late = tr.Module(9000, 9100, "jit_topk_dense(7)", host=1500, ops=[(9000, 9100, "%x = f32[1]")])
    lost = tr.Module(8000, 8100, "jit_topk_dense(7)", host=None, ops=[(8000, 8100, "%x = f32[1]")])
    return tr.Trace(devices=[[c, a, b, lost, late]], spans=SPANS)


def test_busy_is_the_union_of_ops_enqueued_in_the_window():
    r = tr.reduce(synthetic())
    assert r.window_s == pytest.approx(1010e-9)
    assert r.busy_s == pytest.approx((5 + 10 + 25 + 40) * 1e-9)  # the gap inside `a` is idle
    assert r.executions == 3 and r.unplaced == 1


def test_device_time_inside_spans_follows_the_enqueue_not_the_clock():
    r = tr.reduce(synthetic())
    assert r.device_s["index.topk"] == pytest.approx(75e-9)
    assert r.device_s["embed_batch_fn"] == pytest.approx(5e-9)
    assert r.device_s["route_batch"] == pytest.approx(80e-9)


def test_idle_gaps_are_named_after_what_the_host_was_doing():
    r = tr.reduce(synthetic())
    # before c: host [0, 150] mostly the client waiting; before a: [150, 250] mostly
    # embedding; before b: [290, 750] mostly waiting; tail: [790, 1010] mostly waiting
    assert r.idle_gaps == [("window", pytest.approx(460e-9)), ("window", pytest.approx(220e-9)),
                           ("window", pytest.approx(150e-9)),
                           ("embed_batch_fn", pytest.approx(100e-9))]
    assert r.idle_by_span["embed_batch_fn"] == pytest.approx(100e-9)


def test_top_ops_are_named_by_program_and_op():
    names = [n for n, _ in tr.reduce(synthetic()).top_ops]
    assert names[0] == "jit_topk_dense:fusion"
    assert "jit_topk_dense:custom-call[TopK]" in names and "jit_embed:fusion" in names


def test_one_window_span_is_required():
    t = synthetic()
    t.spans = t.spans[1:]
    with pytest.raises(ValueError):
        tr.reduce(t)


def test_merge_flatten_label():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    segs = tr.flatten([(0, 10, "a"), (2, 4, "b"), (3, 4, "c"), (6, 8, "b")])
    assert segs == [(0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 6, "a"), (6, 8, "b"), (8, 10, "a")]
    starts = [s for s, _, _ in segs]
    assert tr.label(2, 4, segs, starts) in ("b", "c")
    assert tr.label(5.5, 9, segs, starts) == "b"
    assert tr.label(20, 30, segs, starts) == tr.OUTSIDE


def test_recorded_chip_trace():
    """Six `route_batch` calls at 2,413 tools, recorded on a TPU v5 lite."""
    t = tr.load(str(FIXTURE))
    r = tr.reduce(t)
    assert len(t.devices) == 1 and r.unplaced == 0 and r.executions == 6
    ops = [(a, b) for m in t.devices[0] for a, b, _ in m.ops]
    naive = sum(b - a for a, b in ops)
    assert 0 < r.busy_s * 1e9 <= naive and r.busy_s < r.window_s
    # every execution came from an index call, none from embedding
    assert r.device_s["index.topk"] == pytest.approx(r.busy_s)
    assert r.device_s.get("embed_batch_fn", 0.0) == 0.0
    assert {n for n, _ in r.top_ops} >= {"jit_topk_dense:fusion",
                                         "jit_topk_dense:custom-call[TopK]"}
    # the window is idle but for the executions' ops
    assert sum(s for _, s in r.idle_gaps) <= r.window_s - r.busy_s + 1e-9
