"""Mean time of the score + top-K phase per `route_batch` call in the window (ms).

Read from the gateway's `route_phase_ms{phase=score}` histogram: the
window's change of its exact sum over the change of its exact count. The
phase ends when the top-K is back on the host, so it includes the wait for
the device.
"""


def read(ctx):
    return ctx.phase_ms_per_batch("score")
