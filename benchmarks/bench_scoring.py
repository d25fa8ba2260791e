"""Candidate-scoring throughput benchmark: per-candidate vs batched vs
coalesced predict, plus the mixed-length fusion comparison.

Measures candidates-scored/sec through the live executor for three modes:

  per-candidate  one ``predict`` task per candidate (the seed hot path)
  batched        one ``predict_batch`` task of n_candidates rows per
                 pipeline (vectorized top-k scoring)
  coalesced      batched + cross-pipeline task coalescing: queued
                 ``predict_batch`` tasks with the same bucketed shape fuse
                 into one device batch; reports batch occupancy

``--mixed-lengths`` instead benchmarks the realistic campaign where every
pipeline's receptor has a *different* length:

  fragmented     legacy exact-length payloads — the coalescer requires an
                 exact (L, chain_split) match, so nothing fuses and the
                 run degenerates to per-length mini-batches
  fused          masked length-bucketed payloads (per-row seq_lens /
                 chain_splits): all lengths pad to a dense bucket edge and
                 fuse into full device batches; reports ``len_occupancy``
                 (real tokens / padded tokens)

  PYTHONPATH=src python benchmarks/bench_scoring.py \
      [--smoke] [--mixed-lengths] [--json BENCH_scoring.json]
"""

from __future__ import annotations

import argparse
import threading
import time

import jax
import numpy as np

from repro.core import ProteinPayload, Task
from repro.core.payload import batch_log, predict_batch_coalesce_rule
from repro.runtime.allocator import choose_length_buckets
from repro.session import CampaignSpec, ImpressSession

try:        # package-style (python -m benchmarks.run)
    from benchmarks._impress import write_bench_json
except ImportError:   # direct script run (python benchmarks/bench_scoring.py)
    from _impress import write_bench_json

MODES = ("per-candidate", "batched", "coalesced")
MIXED_MODES = ("fragmented", "fused")


def run_mode(payload, mode, *, n_pipelines, n_cand, length, split,
             trace=False):
    """Score n_pipelines × n_cand candidates through the executor; returns
    (seconds, coalesce stats). A blocker task holds the device while the
    scoring tasks queue up, so the coalesced mode has a backlog to fuse —
    the steady-state shape of many concurrent pipelines.

    The session facade does the wiring (allocator/executor/payload
    registry — the shared ``payload`` keeps one compile cache across
    modes); raw tasks are then submitted directly, bypassing any protocol.
    ``trace=True`` enables span tracing (the telemetry-overhead probe; no
    trace file is written since ``run()`` is never called here).
    """
    sess = ImpressSession(
        CampaignSpec(protocols=(), receptor_len=length, max_workers=4,
                     coalesce=False,
                     trace_dir="unused-trace-probe" if trace else None),
        payload=payload)
    ex = sess.executor
    if mode == "coalesced":
        ex.register_coalescable("predict_batch",
                                predict_batch_coalesce_rule())
    gate = threading.Event()
    ex.register("blocker", lambda sm, p: gate.wait(timeout=60))
    ex.submit(Task(kind="blocker", payload={}))
    time.sleep(0.05)

    rng = np.random.default_rng(0)
    tasks = []
    for _ in range(n_pipelines):
        tgt = rng.normal(size=16).astype(np.float32)
        seqs = rng.integers(1, 20, size=(n_cand, length)).astype(np.int32)
        if mode == "per-candidate":
            tasks += [Task(kind="predict", payload={
                "sequence": seqs[c], "target": tgt, "receptor_len": split})
                for c in range(n_cand)]
        else:
            tasks.append(Task(kind="predict_batch", payload={
                "sequences": seqs, "target": tgt, "receptor_len": split}))
    for t in tasks:
        ex.submit(t)
    t0 = time.perf_counter()
    gate.set()
    for _ in range(len(tasks) + 1):     # + the blocker
        if ex.drain(timeout=120) is None:
            raise RuntimeError(f"bench mode {mode}: executor stalled")
    dt = time.perf_counter() - t0
    stats = ex.coalesce_stats()
    sess.shutdown()
    return dt, stats


def run_mixed_mode(payload, mode, *, n_pipelines, n_cand, lengths, buckets):
    """Score a mixed-length campaign's backlog: one predict_batch task per
    pipeline, every pipeline at its own sequence length. ``fragmented``
    submits legacy exact-length payloads (the pre-length-bucketing
    behavior: distinct lengths never fuse); ``fused`` submits masked
    payloads that pad to ``buckets`` edges and fuse densely. Returns
    (seconds, coalesce stats incl. per-dispatch len_occupancy)."""
    sess = ImpressSession(
        CampaignSpec(protocols=(), receptor_len=max(lengths), max_workers=4,
                     coalesce=False),
        payload=payload)
    ex = sess.executor
    payload.length_buckets = tuple(buckets)
    ex.register_coalescable("predict_batch",
                            predict_batch_coalesce_rule(
                                length_buckets=buckets))
    gate = threading.Event()
    ex.register("blocker", lambda sm, p: gate.wait(timeout=60))
    ex.submit(Task(kind="blocker", payload={}))
    time.sleep(0.05)

    rng = np.random.default_rng(0)
    log_start = len(batch_log)
    tasks = []
    for i in range(n_pipelines):
        L = int(lengths[i])
        split = max(1, L - 4)
        tgt = rng.normal(size=16).astype(np.float32)
        seqs = rng.integers(1, 20, size=(n_cand, L)).astype(np.int32)
        p = {"sequences": seqs, "target": tgt, "receptor_len": split}
        if mode == "fused":
            p["seq_lens"] = np.full(n_cand, L, np.int32)
            p["chain_splits"] = np.full(n_cand, split, np.int32)
        tasks.append(Task(kind="predict_batch", payload=p))
    for t in tasks:
        ex.submit(t)
    t0 = time.perf_counter()
    gate.set()
    for _ in range(len(tasks) + 1):     # + the blocker
        if ex.drain(timeout=120) is None:
            raise RuntimeError(f"bench mixed mode {mode}: executor stalled")
    dt = time.perf_counter() - t0
    stats = ex.coalesce_stats()
    stats["len_occupancy"] = [b["len_occupancy"]
                              for b in batch_log[log_start:]]
    sess.shutdown()
    return dt, stats


def measure_telemetry_overhead(args, payload):
    """Traced vs untraced wall time on the same coalesced-scoring workload:
    the fractional cost of leaving span tracing on. Expected well under a
    few percent — every span call is a dict append next to a jitted device
    dispatch. The probe scales the backlog up (×4 pipelines) and
    interleaves best-of-pairs so scheduler jitter, which dwarfs the
    tracing cost on millisecond workloads, mostly cancels."""
    kw = dict(n_pipelines=4 * args.pipelines, n_cand=args.n_candidates,
              length=args.length, split=max(1, args.length - 4))
    run_mode(payload, "coalesced", **kw)          # warmup: compile cache
    offs, ons = [], []
    for _ in range(max(3, args.repeats)):
        offs.append(run_mode(payload, "coalesced", **kw)[0])
        ons.append(run_mode(payload, "coalesced", trace=True, **kw)[0])
    return (min(ons) - min(offs)) / min(offs)


def run_mixed(args, payload, record):
    """The --mixed-lengths comparison: fused length-bucketed scoring vs the
    exact-length-match baseline on the same mixed-length backlog."""
    n_cand, n_pipe = args.n_candidates, args.pipelines
    Lmax = args.length
    # every pipeline gets its own length (the realistic campaign: every
    # designable protein is a different size), spread over ~25% below Lmax
    span = max(2, min(n_pipe, Lmax // 4))
    lengths = [Lmax - (i % span) for i in range(n_pipe)]
    buckets = choose_length_buckets(lengths, max_pad=0.25)
    total = n_pipe * n_cand

    results = {}
    for mode in MIXED_MODES:
        run_mixed_mode(payload, mode, n_pipelines=n_pipe, n_cand=n_cand,
                       lengths=lengths, buckets=buckets)   # warmup: compile
        best, stats = min(
            (run_mixed_mode(payload, mode, n_pipelines=n_pipe,
                            n_cand=n_cand, lengths=lengths, buckets=buckets)
             for _ in range(args.repeats)), key=lambda r: r[0])
        results[mode] = (total / best, stats)

    print("mode,cands_per_sec,derived")
    base = results["fragmented"][0]
    for mode in MIXED_MODES:
        cps, stats = results[mode]
        extra = [f"speedup={cps / base:.2f}x",
                 f"dispatches={stats['dispatches']}"]
        occ = stats["len_occupancy"]
        extra.append(f"len_occupancy={np.mean(occ):.2f}" if occ
                     else "len_occupancy=n/a")
        print(f"{mode},{cps:.1f},{';'.join(extra)}")
    speedup = results["fused"][0] / base
    len_occ = float(np.mean(results["fused"][1]["len_occupancy"]))
    print(f"# fused vs fragmented over lengths {min(lengths)}..{Lmax} "
          f"(buckets {buckets}): {speedup:.2f}x, len_occupancy "
          f"{len_occ:.2f} {'(>= 2.5x target met)' if speedup >= 2.5 else ''}")
    record["mixed"] = {
        "lengths": [int(v) for v in lengths],
        "length_buckets": [int(b) for b in buckets],
        "candidates_per_sec": {m: results[m][0] for m in MIXED_MODES},
        "speedup_fused_vs_fragmented": speedup,
        "len_occupancy": len_occ,
        "dispatches": {m: results[m][1]["dispatches"]
                       for m in MIXED_MODES},
    }
    return speedup


def main(emit=print, argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-candidates", type=int, default=None)
    ap.add_argument("--pipelines", type=int, default=None)
    ap.add_argument("--length", type=int, default=None)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes + single repeat (CI)")
    ap.add_argument("--mixed-lengths", action="store_true",
                    help="benchmark fused mixed-length scoring vs the "
                         "exact-length-match baseline")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a machine-readable result record "
                         "(BENCH_scoring.json)")
    args = ap.parse_args(argv)
    # mixed defaults: many pipelines, small per-pipeline top-k — the
    # steady state where per-length fragmentation hurts most
    if args.n_candidates is None:
        args.n_candidates = 4 if args.mixed_lengths else 8
    if args.pipelines is None:
        args.pipelines = 16 if args.mixed_lengths else 4
    if args.length is None:
        args.length = 64 if args.mixed_lengths else 16
    if min(args.n_candidates, args.pipelines, args.length,
           args.repeats) < 1:
        ap.error("--n-candidates/--pipelines/--length/--repeats must be >= 1")
    if args.smoke:
        args.repeats = 1
        if args.mixed_lengths:
            args.n_candidates, args.pipelines, args.length = 2, 4, 16
        else:
            args.n_candidates, args.pipelines, args.length = 4, 2, 12

    n_cand, n_pipe, length = args.n_candidates, args.pipelines, args.length
    payload = ProteinPayload(jax.random.PRNGKey(0), reduced=True,
                             length=length)
    record = {"bench": "scoring", "schema": 1, "smoke": bool(args.smoke),
              "n_candidates": n_cand, "pipelines": n_pipe, "length": length}

    if args.mixed_lengths:
        speedup = run_mixed(args, payload, record)
        if args.json:
            write_bench_json(args.json, record)
        return speedup

    split = max(1, length - 4)
    total = n_pipe * n_cand

    results = {}
    for mode in MODES:
        run_mode(payload, mode, n_pipelines=n_pipe, n_cand=n_cand,
                 length=length, split=split)       # warmup: compile cache
        best, stats = min(
            (run_mode(payload, mode, n_pipelines=n_pipe, n_cand=n_cand,
                      length=length, split=split)
             for _ in range(args.repeats)), key=lambda r: r[0])
        results[mode] = (total / best, stats)

    print("mode,cands_per_sec,derived")
    base = results["per-candidate"][0]
    occupancy = None
    for mode in MODES:
        cps, stats = results[mode]
        extra = [f"speedup={cps / base:.2f}x"]
        if mode == "coalesced":
            occ = [b["occupancy"] for b in batch_log[-stats["dispatches"]:]] \
                if stats["dispatches"] else []
            occupancy = float(np.mean(occ)) if occ else None
            extra.append(f"occupancy={occupancy:.2f}" if occ
                         else "occupancy=n/a")
            extra.append(
                f"tasks_per_dispatch={stats['mean_tasks_per_dispatch']:.1f}")
        emit(f"{mode},{cps:.1f},{';'.join(extra)}")
    speedup = results["batched"][0] / base
    print(f"# batched vs per-candidate at n_candidates={n_cand}: "
          f"{speedup:.2f}x {'(>= 3x target met)' if speedup >= 3 else ''}")
    if args.json:
        overhead = measure_telemetry_overhead(args, payload)
        print(f"# telemetry_overhead (tracing on vs off): "
              f"{100 * overhead:+.1f}%")
        record.update({
            "candidates_per_sec": {m: results[m][0] for m in MODES},
            "speedup_vs_per_candidate": {
                m: results[m][0] / base for m in MODES},
            "occupancy": occupancy,
            "telemetry_overhead": overhead,
        })
        write_bench_json(args.json, record)
    return speedup


if __name__ == "__main__":
    from repro.session import enable_compilation_cache
    enable_compilation_cache()
    main()
