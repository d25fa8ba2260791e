"""Host layer: the share of the window the interpreter spent in garbage
collection (the ``impress.gc`` span), from the ``gc.pause_s`` histograms
of every generation in the window's session registries, host clock (%).
A program that records no GC pause gives nothing."""


def read(ctx):
    run = ctx["run"]
    hists = [h for reg in run.registries
             for h in reg.series("gc.pause_s").values()]
    if not hists:
        return None
    return 100.0 * sum(h.sum for h in hists) / (run.t1 - run.t0)
