"""Heterogeneous-pipeline benchmark: staged binder campaign sharing the
devices with a fold-flood co-tenant, fair scheduling vs naive FIFO.

The workload is the paper's heterogeneous steady state: a three-stage
binder protocol (backbone-sample -> sequence-design -> fold/score, two
param sets) runs while several rescore co-tenants flood the fold stage
with batched scoring rounds on the same executor (fold dispatches capped
at ``--fold-max-rows`` rows — the device-memory bound that keeps a real
fold model's batches finite). Both modes run the identical
campaign; the only difference is whether the stage tables' priority-band
shares are pushed into the task queue (``CampaignSpec.fair_scheduling``):

  fifo   legacy priority/insertion order — fold-flood tasks queue ahead
         of the binder's sampling work in long runs
  fair   weighted-fair pick across the stage bands — the sampling trickle
         keeps flowing through the flood

Reported per mode: campaign makespan, mixed-stage task throughput, and
per-stage dispatch/wait/utilization sections straight from the stage
report. The derived line compares the binder sampling stages' mean queue
wait across modes — the fairness claim as one number.

  PYTHONPATH=src python benchmarks/bench_pipeline.py [--smoke] [--json P]
"""

from __future__ import annotations

import argparse

import jax

from repro.core import ProteinPayload
from repro.session import CampaignSpec, ImpressSession, ProtocolSpec

MODES = ("fifo", "fair")
SAMPLING_STAGES = ("backbone", "seqdesign")   # the binder's band-0 stages


def run_campaign(payload, fair, *, structures, binder_cycles, n_candidates,
                 rescore_tenants, rescore_rounds, rescore_rows,
                 fold_max_rows, max_workers, timeout):
    # several rescore co-tenants deepen the fold backlog (each pipeline
    # keeps one task in flight); the per-stage dispatch row cap
    # (device-memory bound) keeps the coalescer from draining the whole
    # flood in one fused dispatch — the regime fair scheduling is for
    spec = CampaignSpec(
        structures=structures, receptor_len=payload.length, peptide_len=6,
        protocols=(
            ProtocolSpec("binder", n_cycles=binder_cycles,
                         n_candidates=n_candidates, score_batch=2),)
        + tuple(
            ProtocolSpec("rescore", name=f"rescore{i}",
                         n_cycles=rescore_rounds, score_batch=rescore_rows,
                         stage_max_rows=fold_max_rows)
            for i in range(rescore_tenants)),
        seed=0, reduced=True, max_workers=max_workers, timeout=timeout,
        fair_scheduling=fair)
    with ImpressSession(spec, payload=payload) as sess:
        report = sess.run().to_dict()
    return report


def stage_metrics(report):
    """Flatten the report's stage sections into the numbers the bench
    compares: per-stage mean queue wait and the mixed-stage totals."""
    stages = {k: v for k, v in report["stages"].items()
              if not k.startswith("__")}
    out = {}
    for name, s in stages.items():
        out[name] = {
            "tasks": s["tasks"], "dispatches": s["dispatches"],
            "rows": s["rows"],
            "mean_wait_s": s["wait_s"] / max(s["tasks"], 1),
            "utilization": s.get("utilization", 0.0),
        }
    total_tasks = sum(s["tasks"] for s in stages.values())
    return out, total_tasks


def main(emit=print, argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--structures", type=int, default=4)
    ap.add_argument("--binder-cycles", type=int, default=3)
    ap.add_argument("--n-candidates", type=int, default=4)
    ap.add_argument("--rescore-tenants", type=int, default=3)
    ap.add_argument("--rescore-rounds", type=int, default=8)
    ap.add_argument("--rescore-rows", type=int, default=4)
    ap.add_argument("--fold-max-rows", type=int, default=8)
    ap.add_argument("--length", type=int, default=16)
    ap.add_argument("--max-workers", type=int, default=2)
    ap.add_argument("--timeout", type=float, default=900.0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes (CI)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a machine-readable result record "
                         "(BENCH_pipeline.json)")
    args = ap.parse_args(argv)
    if min(args.structures, args.binder_cycles, args.n_candidates,
           args.rescore_tenants, args.rescore_rounds,
           args.rescore_rows, args.fold_max_rows) < 1:
        ap.error("all workload sizes must be >= 1")
    if args.smoke:
        args.structures, args.binder_cycles = 2, 1
        args.n_candidates, args.rescore_tenants = 2, 2
        args.rescore_rounds, args.rescore_rows = 2, 2
        args.fold_max_rows, args.length = 4, 12

    kw = dict(structures=args.structures, binder_cycles=args.binder_cycles,
              n_candidates=args.n_candidates,
              rescore_tenants=args.rescore_tenants,
              rescore_rounds=args.rescore_rounds,
              rescore_rows=args.rescore_rows,
              fold_max_rows=args.fold_max_rows,
              max_workers=args.max_workers, timeout=args.timeout)
    payload = ProteinPayload(jax.random.PRNGKey(0), reduced=True,
                             length=args.length)
    run_campaign(payload, True, **kw)        # warmup: fill compile cache

    results = {}
    print("mode,tasks_per_sec,derived")
    for mode in MODES:
        report = run_campaign(payload, mode == "fair", **kw)
        stages, total_tasks = stage_metrics(report)
        makespan = report["makespan_s"]
        results[mode] = {"makespan_s": makespan,
                         "tasks_per_sec": total_tasks / max(makespan, 1e-9),
                         "utilization": report["utilization"],
                         "stages": stages}
        waits = ";".join(
            f"{n}_wait_ms={s['mean_wait_s'] * 1e3:.1f}"
            for n, s in sorted(stages.items()))
        emit(f"{mode},{results[mode]['tasks_per_sec']:.1f},"
             f"makespan_s={makespan:.2f};{waits}")

    def sampling_wait(mode):
        ss = results[mode]["stages"]
        picked = [ss[n] for n in SAMPLING_STAGES if n in ss]
        return (sum(s["mean_wait_s"] * s["tasks"] for s in picked)
                / max(sum(s["tasks"] for s in picked), 1))

    fifo_w, fair_w = sampling_wait("fifo"), sampling_wait("fair")
    ratio = fifo_w / max(fair_w, 1e-9)
    print(f"# binder sampling-stage mean wait: fifo={fifo_w * 1e3:.1f}ms "
          f"fair={fair_w * 1e3:.1f}ms ({ratio:.2f}x"
          f"{' — fair scheduling wins' if ratio >= 1.0 else ''})")
    if args.json:
        try:
            from benchmarks._impress import write_bench_json
        except ImportError:
            from _impress import write_bench_json
        write_bench_json(args.json, {
            "bench": "pipeline", "schema": 1, "smoke": bool(args.smoke),
            "workload": {k: v for k, v in vars(args).items()
                         if k not in ("json",)},
            "modes": results,
            "sampling_wait_s": {"fifo": fifo_w, "fair": fair_w},
            "sampling_wait_ratio_fifo_vs_fair": ratio,
        })
    return ratio


if __name__ == "__main__":
    from repro.session import enable_compilation_cache
    enable_compilation_cache()
    main()
