"""Benchmark harness: one module per paper table/figure + the roofline
table, plus the throughput benchmarks for the two batched hot stages.
Prints ``name,us_per_call,derived`` CSV lines; the ``scoring``,
``generate``, ``pipeline``, ``gateway`` and ``resilience`` entries
additionally write machine-readable ``BENCH_scoring.json`` /
``BENCH_generate.json`` / ``BENCH_pipeline.json`` /
``BENCH_gateway.json`` / ``BENCH_resilience.json`` records
(candidates/sec, occupancy, speedup vs baseline, per-stage and
per-tenant waits, goodput under faults) — the repo's perf trajectory
across PRs.

  PYTHONPATH=src python -m benchmarks.run [--only table1,scoring,...]
"""

import argparse
import time


def emit(name, us_per_call, derived):
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


BENCHES = ("roofline", "table1", "fig2", "fig45", "fig3", "evolution",
           "scoring", "generate", "pipeline", "gateway", "resilience")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(BENCHES))
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else set(BENCHES)

    print("name,us_per_call,derived")
    t0 = time.time()
    if "roofline" in only:
        from benchmarks import roofline
        roofline.main(emit)
    if "table1" in only:
        from benchmarks import table1_adaptivity
        table1_adaptivity.main(emit)
    if "fig2" in only:
        from benchmarks import fig2_quality
        fig2_quality.main(emit)
    if "fig45" in only:
        from benchmarks import fig45_utilization
        fig45_utilization.main(emit)
    if "fig3" in only:
        from benchmarks import fig3_expansion
        fig3_expansion.main(emit)
    if "evolution" in only:
        from benchmarks import bench_evolution
        bench_evolution.main(emit)
    if "scoring" in only:
        from benchmarks import bench_scoring
        # these two emit their own mode,value,derived CSV lines
        bench_scoring.main(print, argv=["--json", "BENCH_scoring.json"])
        bench_scoring.main(print, argv=["--mixed-lengths", "--json",
                                        "BENCH_scoring_mixed.json"])
    if "generate" in only:
        from benchmarks import bench_generate
        bench_generate.main(print, argv=["--json", "BENCH_generate.json"])
        # paged continuous-decode sweep merges into the same record
        bench_generate.main(print, argv=["--decode-kernel", "--json",
                                         "BENCH_generate.json"])
    if "pipeline" in only:
        from benchmarks import bench_pipeline
        bench_pipeline.main(print, argv=["--json", "BENCH_pipeline.json"])
    if "gateway" in only:
        from benchmarks import bench_gateway
        bench_gateway.main(print, argv=["--json", "BENCH_gateway.json"])
    if "resilience" in only:
        from benchmarks import bench_resilience
        bench_resilience.main(print,
                              argv=["--json", "BENCH_resilience.json"])
    emit("benchmarks.total_wall_s", (time.time() - t0) * 1e6,
         round(time.time() - t0, 1))


if __name__ == "__main__":
    from repro.session import enable_compilation_cache
    enable_compilation_cache()
    main()
