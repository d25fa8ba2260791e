"""Helpers the per-layer metric readers (``bench/metrics/*.py``) share.

A reader is ``read(ctx) -> float | None``; ``ctx`` holds the run
(``ctx["run"]``), the reduced trace (``ctx["trace"]``), the device's
peaks (``ctx["peak"]``) and the run's end-to-end numbers (``ctx["e2e"]``).
A reader that finds nothing to read returns None, and the metric is left
out of the result line.
"""

from __future__ import annotations

import numpy as np

from bench import trace as tracemod

# Names the reduction finds the kernels by. The Pallas paged decode
# kernel's custom call is named after ``ops.paged_decode_attention``. The
# masked scorer is jitted from a ``functools.partial``, so its executables
# are ``jit__unknown``; on the cells' paths no other partial is jitted.
PAGED_KERNEL = ("_paged_decode_attention",)
FOLD_MODULE = ("jit_foldscore_fwd_masked", "jit__unknown")


def histogram_p95(run, name, **labels):
    """p95 of a histogram merged over the run's executor registries."""
    from repro.obs.metrics import Histogram
    merged = Histogram()
    for reg in run.registries:
        for key, h in reg.series(name).items():
            got = dict(key[1:])
            if all(got.get(k) == v for k, v in labels.items()):
                merged.merge(h)
    return merged.quantile(0.95) if merged.count else None


def histogram_mean(run, name, **labels):
    total, count = 0.0, 0
    for reg in run.registries:
        for key, h in reg.series(name).items():
            got = dict(key[1:])
            if all(got.get(k) == v for k, v in labels.items()):
                total += h.sum
                count += h.count
    return total / count if count else None


def rows_per_dispatch(entries):
    return (float(np.mean([e["rows"] for e in entries]))
            if entries else None)


def payload_mfu(ctx):
    """Useful model operations of the window (tokens sampled, residues
    scored, padding excluded), as each model's architecture counts them,
    over window x chips x bf16 peak, in %."""
    run, peak = ctx["run"], ctx["peak"]
    if peak is None:
        return None
    t0, t1 = run.t0, run.extra.get("t_last", run.t1)
    roles = run.roles()
    useful = 0
    for g in run.recorder.gen:
        if t0 <= g["t"] <= t1:
            _, m, arch = roles["generator", g["ns"]]
            useful += sum(arch.generator_flops(m, len(t))
                          for t in g["tokens"])
    for r in run.recorder.scores:
        if t0 <= r["t"] <= t1:
            _, m, arch = roles["scorer", r["ns"]]
            useful += arch.scorer_flops(m, len(r["seq"]))
    if not useful:
        return None
    return 100.0 * useful / ((t1 - t0) * run.chips
                             * float(peak["bf16_flops_per_s"]))


def roofline(ctx, counted: str, times: str, patterns):
    """100 x the least time the counted calls could take over the time
    their events took in the trace. Both cover the slice: the calls are
    counted while ``run.tracing`` is on, inside the slice span, and the
    events' times are clipped to that span."""
    run = ctx["run"]
    t = tracemod.time_matching(ctx["trace"][times], *patterns)
    work = run.traced.get(counted)
    if not t or not work or ctx["peak"] is None:
        return None
    return 100.0 * work[3] / t


def idle_share(ctx):
    """100 x (1 - busy / slice), both clipped to the slice span."""
    red = ctx["trace"]
    if not red["window_s"] or not red["devices"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
