"""Coordinator layer: the share of the window the coordinator spent
routing drained completions (the ``impress.coordinator.handle`` span),
from the ``coordinator.handle_s`` histograms of the window's session
registries, host clock (%). A program without the histogram gives
nothing."""


def read(ctx):
    run = ctx["run"]
    hists = [h for reg in run.registries
             for h in reg.series("coordinator.handle_s").values()]
    if not hists:
        return None
    return 100.0 * sum(h.sum for h in hists) / (run.t1 - run.t0)
