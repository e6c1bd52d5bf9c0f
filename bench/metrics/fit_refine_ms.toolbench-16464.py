"""Host time of the offline fit's OATS-S1 passes and validation gate at set-up (ms).

Read from the program's default registry: the sum of
`fit_phase_ms{phase=refine}` and `fit_phase_ms{phase=gate}` (each span ends
when its result is on the device, ready). The fit runs once per process, at
set-up. Nothing to read where the program records no such phase.
"""
PHASES = ("refine", "gate")


def read(ctx):
    from repro.obs.metrics import get_registry

    hists = [h for h in get_registry().instruments()
             if h.name == "fit_phase_ms" and dict(h.labels).get("phase") in PHASES]
    if not sum(h.count() for h in hists):
        return None
    return sum(h.mean() * h.count() for h in hists)
