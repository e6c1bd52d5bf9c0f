"""Mean host time of the assemble phase per `route_batch` call in the window (ms).

Read from the gateway's `route_phase_ms{phase=assemble}` histogram: the
window's change of its exact sum over the change of its exact count.
"""


def read(ctx):
    return ctx.phase_ms_per_batch("assemble")
