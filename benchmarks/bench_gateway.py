"""Gateway co-tenancy benchmark: fused vs sequential two-tenant serving.

Two tenants run the identical mixed-length binder campaign on one
resident gateway runtime. The only variable is *when* they run:

  sequential   tenant A's campaign runs to completion, then tenant B's —
               the status quo of one-campaign-per-process serving (no
               co-tenant rows exist to fuse with)
  fused        both campaigns are live concurrently — same-bucket
               same-stage tasks from the two tenants coalesce into shared
               device batches (cross-campaign coalescing)

Both modes execute exactly the same task set on the same payload, so the
aggregate-throughput delta is purely the gateway's co-tenancy win: fused
batches fill device batch slots that sequential serving leaves empty.
Quotas are enforced throughout (equal shares), and per-tenant p95 queue
wait comes straight from the tenant-sliced telemetry — the fairness
number co-tenancy must not regress.

Reported per mode: aggregate candidates/sec (accepted trajectories per
wall-second across both tenants), makespan, cross-tenant dispatch count,
and per-tenant p95 queue wait. Derived: fused-over-sequential throughput
ratio (the coalescing win as one number).

  PYTHONPATH=src python benchmarks/bench_gateway.py [--smoke] [--json P]
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.core import ProteinPayload
from repro.gateway import GatewayService, TenantQuota

TENANTS = ("alice", "bob")


def _spec(args, seed):
    return {
        "structures": args.structures,
        "receptor_len": [24, 32],    # cycled per structure -> mixed buckets
        "peptide_len": 8,
        "protocols": [{"kind": "binder", "n_cycles": args.cycles,
                       "n_candidates": args.candidates,
                       "score_batch": args.score_batch}],
        "seed": seed, "reduced": True,
    }


def _wait(gw, cids, timeout):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if all(gw.report(c)["state"] == "COMPLETED" for c in cids):
            return
        time.sleep(0.05)
    raise RuntimeError(f"campaigns {cids} did not finish in {timeout}s")


def run_mode(payload, args, fused):
    gw = GatewayService(
        payload=payload, max_workers=args.max_workers,
        quotas={t: TenantQuota(share=1.0, max_devices=args.device_cap)
                for t in TENANTS})
    gw.start()
    try:
        t0 = time.time()
        cids = []
        for i, tenant in enumerate(TENANTS):
            cid = gw.submit_campaign(_spec(args, seed=i), tenant=tenant)
            cids.append(cid)
            if not fused:                    # sequential: drain before B
                _wait(gw, [cid], args.timeout)
        _wait(gw, cids, args.timeout)
        makespan = time.time() - t0
        reports = {t: gw.report(c) for t, c in zip(TENANTS, cids)}
        stats = gw.coalesce_stats()
        tenants = gw.executor.telemetry_summary().get("tenants", {})
        trajectories = sum(r["trajectories"] for r in reports.values())
        return {
            "makespan_s": makespan,
            "trajectories": trajectories,
            "candidates_per_sec": trajectories / max(makespan, 1e-9),
            "fused_tasks": stats.get("tasks_fused", 0),
            "cross_tenant_dispatches": stats.get(
                "cross_tenant", {}).get("dispatches", 0),
            "p95_queue_wait_s": {
                t: tenants.get(t, {}).get("queue_wait_s", {}).get(
                    "p95", 0.0) for t in TENANTS},
            "quotas": gw.quotas.stats(),
        }
    finally:
        gw.shutdown()


def main(emit=print, argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--structures", type=int, default=3)
    ap.add_argument("--cycles", type=int, default=2)
    ap.add_argument("--candidates", type=int, default=6)
    ap.add_argument("--score-batch", type=int, default=3)
    ap.add_argument("--max-workers", type=int, default=4)
    ap.add_argument("--device-cap", type=int, default=None,
                    help="per-tenant hard device cap (default: uncapped)")
    ap.add_argument("--payload-length", type=int, default=40)
    ap.add_argument("--timeout", type=float, default=600.0)
    ap.add_argument("--smoke", action="store_true", help="tiny sizes (CI)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write BENCH_gateway.json")
    args = ap.parse_args(argv)
    if args.smoke:
        args.structures, args.cycles = 2, 1
        args.candidates, args.score_batch = 4, 2

    payload = ProteinPayload(jax.random.PRNGKey(0), reduced=True,
                             length=args.payload_length)
    # warmup BOTH modes: solo and fused runs coalesce different row
    # compositions into different padded batch shapes, so each mode has
    # its own compile set — measuring either cold would charge XLA's
    # compile wall to the scheduling policy
    run_mode(payload, args, fused=False)
    run_mode(payload, args, fused=True)

    results = {}
    print("mode,candidates_per_sec,derived")
    for mode in ("sequential", "fused"):
        r = run_mode(payload, args, fused=(mode == "fused"))
        results[mode] = r
        waits = ";".join(f"{t}_p95_wait_ms="
                         f"{r['p95_queue_wait_s'][t] * 1e3:.1f}"
                         for t in TENANTS)
        emit(f"{mode},{r['candidates_per_sec']:.2f},"
             f"makespan_s={r['makespan_s']:.2f};"
             f"xt_dispatches={r['cross_tenant_dispatches']};{waits}")

    ratio = (results["fused"]["candidates_per_sec"]
             / max(results["sequential"]["candidates_per_sec"], 1e-9))
    xt = results["fused"]["cross_tenant_dispatches"]
    print(f"# fused vs sequential: {ratio:.2f}x aggregate candidates/sec, "
          f"{xt} cross-tenant fused dispatches"
          f"{' — co-tenancy wins' if ratio >= 1.0 else ''}")
    if args.json:
        try:
            from benchmarks._impress import write_bench_json
        except ImportError:
            from _impress import write_bench_json
        write_bench_json(args.json, {
            "bench": "gateway", "schema": 1, "smoke": bool(args.smoke),
            "workload": {k: v for k, v in vars(args).items()
                         if k not in ("json",)},
            "modes": results,
            "fused_vs_sequential_candidates_per_sec": ratio,
        })
    return ratio


if __name__ == "__main__":
    from repro.session import enable_compilation_cache
    enable_compilation_cache()
    main()
