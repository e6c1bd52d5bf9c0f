"""Profiler trace -> device busy time, device time inside host spans, idle gaps.

Input is the `.xplane.pb` that `jax.profiler` writes, read with
`jax.profiler.ProfileData`. What is taken from it:

  device ops   events of each `/device:TPU:<n>` plane's "XLA Ops" line, and the
               program executions that hold them ("XLA Modules", with a run id);
  host spans   `TraceAnnotation` events the benchmark records around its calls
               into each layer (`window`, `route_batch`, `embed_batch_fn`,
               `index.topk`), all on the client's one thread;
  enqueue      `DoEnqueueProgram` host events, which carry the run id of the
               program execution they put on the device.

The device's clock and the host's are aligned in the trace only to within
about a millisecond, which is longer than a whole program at 2,413 tools.
So a program execution is placed on the host's timeline by its run id (the
host time it was enqueued), never by comparing the two clocks:

  busy_s             union of the device ops of every execution enqueued
                     inside the window span;
  device_s(name)     union of the device ops of every execution enqueued
                     inside a span of that name;
  idle gaps          the stretches between busy intervals, each laid on the
                     host's timeline as the gap's length before the next
                     execution was enqueued, and named after the innermost
                     host span that covers most of it ("window" alone means
                     the client was waiting for arrivals).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
SPANS = ("window", "route_batch", "embed_batch_fn", "index.topk")
WINDOW = "window"
OUTSIDE = "outside"


@dataclasses.dataclass
class Module:
    start: float  # device clock, ns
    end: float
    name: str
    host: Optional[float]  # host time it was enqueued, ns (None: no enqueue event found)
    ops: List[Tuple[float, float, str]] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Trace:
    devices: List[List[Module]]  # per device, executions sorted by start
    spans: List[Tuple[float, float, str]]  # host spans, host clock ns


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint union of intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(merged: Sequence[Interval]) -> float:
    return float(sum(b - a for a, b in merged))


def covered(t: float, merged: Sequence[Interval]) -> bool:
    i = bisect.bisect_right(merged, (t, float("inf"))) - 1
    return i >= 0 and merged[i][0] <= t <= merged[i][1]


def flatten(spans: Sequence[Tuple[float, float, str]]) -> List[Tuple[float, float, str]]:
    """Properly nested spans -> disjoint segments named after the innermost span."""
    segs: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []
    cur = float("-inf")

    def close_until(t):
        nonlocal cur
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cur:
                segs.append((cur, end, name))
                cur = end

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        close_until(s)
        if stack and s > cur:
            segs.append((cur, s, stack[-1][1]))
        cur = max(cur, s)
        stack.append((e, name))
    close_until(float("inf"))
    return segs


def label(a: float, b: float, segs: Sequence[Tuple[float, float, str]],
          starts: Sequence[float]) -> str:
    """The segment name covering most of [a, b] (OUTSIDE where none does)."""
    if b <= a:
        return OUTSIDE
    share: Dict[str, float] = collections.defaultdict(float)
    i = max(0, bisect.bisect_right(starts, a) - 1)
    inside = 0.0
    while i < len(segs) and segs[i][0] < b:
        s, e, name = segs[i]
        ov = min(e, b) - max(s, a)
        if ov > 0:
            share[name] += ov
            inside += ov
        i += 1
    share[OUTSIDE] += (b - a) - inside
    return max(share.items(), key=lambda kv: kv[1])[0]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float  # averaged over devices
    device_s: Dict[str, float]  # per span name, summed over devices
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]  # the longest, by host span
    idle_by_span: Dict[str, float]
    executions: int
    unplaced: int  # executions with no enqueue event (left out of every number)


def _short(op: str, module: str) -> str:
    head = op.split(" = ", 1)[0].lstrip("%")
    target = re.search(r'custom_call_target="([^"]+)"', op)
    mod = module.split("(", 1)[0]
    return f"{mod}:{head}" + (f"[{target.group(1)}]" if target else "")


def reduce(trace: Trace, top: int = 10) -> Reduced:
    """Reduce one trace to the window's numbers (see the module docstring)."""
    windows = [(s, e) for s, e, n in trace.spans if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found {len(windows)}")
    w0, w1 = windows[0]
    by_name = {n: merge([(s, e) for s, e, m in trace.spans if m == n])
               for n in {m for _, _, m in trace.spans}}
    segs = flatten(trace.spans)
    starts = [s for s, _, _ in segs]
    device_s: Dict[str, float] = collections.defaultdict(float)
    op_time: Dict[str, float] = collections.defaultdict(float)
    idle_by: Dict[str, float] = collections.defaultdict(float)
    gaps: List[Tuple[str, float]] = []
    busy_total = 0.0
    n_exec = unplaced = 0
    for modules in trace.devices:
        placed = [m for m in modules if m.host is not None and w0 <= m.host <= w1]
        unplaced += sum(m.host is None for m in modules)
        n_exec += len(placed)
        busy = merge([(a, b) for m in placed for a, b, _ in m.ops])
        busy_total += length(busy)
        for name, spans in by_name.items():
            if name != WINDOW:
                inside = [m for m in placed if covered(m.host, spans)]
                device_s[name] += length(merge([(a, b) for m in inside for a, b, _ in m.ops]))
        for m in placed:
            for a, b, op in m.ops:
                op_time[_short(op, m.name)] += (b - a) / 1e9
        # idle stretches on the host's timeline: before each execution the
        # gap since the previous busy interval ended, ending where it was
        # enqueued; then the tail from the last busy interval to the window's end
        placed.sort(key=lambda m: m.start)
        prev_end, prev_host_end = None, w0
        for m in placed:
            if not m.ops:
                continue
            first = min(a for a, _, _ in m.ops)
            gap = (first - prev_end) if prev_end is not None else (m.host - w0)
            if gap > 0:
                name = label(m.host - gap, m.host, segs, starts)
                gaps.append((name, gap / 1e9))
                idle_by[name] += gap / 1e9
            last = max(b for _, b, _ in m.ops)
            if prev_end is None or last > prev_end:
                prev_end = last
                prev_host_end = m.host + (last - first)
        tail = w1 - prev_host_end
        if tail > 0:
            name = label(prev_host_end, w1, segs, starts)
            gaps.append((name, tail / 1e9))
            idle_by[name] += tail / 1e9
    n_dev = max(1, len(trace.devices))
    gaps.sort(key=lambda g: -g[1])
    return Reduced(
        window_s=(w1 - w0) / 1e9,
        busy_s=busy_total / 1e9 / n_dev,
        device_s={k: v / 1e9 for k, v in device_s.items()},
        top_ops=sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=gaps[:top],
        idle_by_span=dict(idle_by),
        executions=n_exec,
        unplaced=unplaced,
    )


def load(path: str, span_names: Sequence[str] = SPANS) -> Trace:
    """Read a `.xplane.pb` into device executions, their ops, and host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    enqueued: Dict[Tuple[int, int], float] = {}
    spans: List[Tuple[float, float, str]] = []
    dev_planes = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev_planes.append((int(m.group(1)), plane))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in span_names:
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
                    elif ev.name == "DoEnqueueProgram":
                        st = dict(ev.stats)
                        enqueued[(int(st.get("device_ordinal", 0)), int(st["run_id"]))] = ev.start_ns
    devices = []
    for ordinal, plane in sorted(dev_planes, key=lambda x: x[0]):
        lines = {line.name: line for line in plane.lines}
        if "XLA Modules" not in lines:
            continue
        modules = []
        for ev in lines["XLA Modules"].events:
            run_id = dict(ev.stats).get("run_id")
            host = None if run_id is None else enqueued.get((ordinal, int(run_id)))
            modules.append(Module(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, host))
        modules.sort(key=lambda x: x.start)
        starts = [x.start for x in modules]
        for ev in lines["XLA Ops"].events if "XLA Ops" in lines else ():
            i = bisect.bisect_right(starts, ev.start_ns) - 1
            if i >= 0 and ev.start_ns <= modules[i].end:
                modules[i].ops.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
        devices.append(modules)
    return Trace(devices=devices, spans=spans)
