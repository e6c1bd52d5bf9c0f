"""Mean host time of the embed phase per `route_batch` call in the window (ms).

Read from the gateway's `route_phase_ms{phase=embed}` histogram: the
window's change of its exact sum over the change of its exact count.
"""


def read(ctx):
    return ctx.phase_ms_per_batch("embed")
