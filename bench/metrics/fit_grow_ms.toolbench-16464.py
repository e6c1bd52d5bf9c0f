"""Host time of growing the registry past the fitted tools at set-up (ms).

Read from the program's default registry: the sum of
`fit_phase_ms{phase=grow}`, the span around the tiled, perturbed table
(`scale_tool_corpus`) and the registry's tool records. It runs once per
process, at set-up. Nothing to read where the program records no such phase.
"""
PHASES = ("grow",)


def read(ctx):
    from repro.obs.metrics import get_registry

    hists = [h for h in get_registry().instruments()
             if h.name == "fit_phase_ms" and dict(h.labels).get("phase") in PHASES]
    if not sum(h.count() for h in hists):
        return None
    return sum(h.mean() * h.count() for h in hists)
