"""Share of the traced window in which no op ran on the device (%).

1 - (union of the device ops of every execution enqueued in the window)
over the window's length, averaged over the chips used.
"""


def read(ctx):
    r = ctx.reduced
    if r is None or r.window_s <= 0 or r.executions == 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
