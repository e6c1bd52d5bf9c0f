"""Share of its roofline that the score + top-K step reaches in the window (%).

The least time the chip could take for every `index.topk` call of the
window (`bench.work`: the larger of flops over peak FLOP/s and bytes over
peak HBM bytes/s, for the real query rows of each call) over the device
time of every op that the benchmark's `index.topk` spans enqueued, whatever
kernel implements them. Nothing to read without a trace or without one
device execution inside those spans.
"""
from bench.work import least_seconds, topk_work


def read(ctx):
    if ctx.reduced is None or not ctx.score_calls or ctx.peaks is None:
        return None
    device_s = ctx.reduced.device_s.get("index.topk", 0.0)
    if device_s <= 0:
        return None
    least = sum(least_seconds(*topk_work(q, t, d, k), ctx.peaks) for q, t, d, k in ctx.score_calls)
    return 100.0 * least / device_s
