"""Generate-stage throughput benchmark: per-pipeline vs batched vs
continuous (rolling-admission) sampling.

Measures sequences-sampled/sec through the live executor for three modes:

  per-pipeline  one ``generate`` task per pipeline cycle (the seed path)
  batched       one-row ``generate_batch`` tasks fused at dequeue time:
                a queued backlog stacks into one vmapped device dispatch
  continuous    rolling admission: tasks submitted *after* a batch leader
                was dequeued still join its device batch during the
                admission window — no backlog needed, the steady-state
                shape of pipelines finishing cycles at different times

  PYTHONPATH=src python benchmarks/bench_generate.py [--smoke]

``--decode-kernel`` instead sweeps *occupancy* of one resident paged
continuous-decode engine (Pallas decode kernel + paged KV cache, the
vectorized fallback on CPU): capacity stays fixed while live rows grow,
measuring per-sequence decode throughput as the in-flight batch fills —
the continuous-batching record (tokens/s per row holds flat-to-rising
as rows are admitted, aggregate tokens/s scales with occupancy, and the
trace counters prove zero recompiles across the sweep). Results merge
into BENCH_generate.json under ``decode_kernel``.
"""

from __future__ import annotations

import argparse
import threading
import time

import jax
import numpy as np

from repro.core import ProteinPayload, ResourceRequest, Task
from repro.core.payload import gen_batch_log, generate_batch_coalesce_rule
from repro.session import CampaignSpec, ImpressSession

MODES = ("per-pipeline", "batched", "continuous")


def make_tasks(mode, *, n_pipelines, n_cand, length, rng):
    tasks = []
    for i in range(n_pipelines):
        bb = rng.normal(size=(length + 6, 16)).astype(np.float32)
        if mode == "per-pipeline":
            tasks.append(Task(kind="generate", payload={
                "backbone": bb, "n": n_cand, "length": length,
                "temperature": 1.0, "seed": i}))
        else:
            tasks.append(Task(kind="generate_batch", payload={
                "backbones": bb[None], "seeds": [i], "n": n_cand,
                "length": length, "temperature": 1.0},
                resources=ResourceRequest(n_devices=1, rows=1)))
    return tasks


def run_mode(payload, mode, *, n_pipelines, n_cand, length):
    """Sample n_pipelines × n_cand sequences through the executor; returns
    (seconds, coalesce stats). The backlog modes hold the device with a
    blocker while tasks queue; the continuous mode submits with no backlog
    at all and relies on rolling admission to fuse the stream.

    The session facade does the wiring (allocator/executor/payload
    registry — the shared ``payload`` keeps one compile cache across
    modes); each mode then registers its own coalesce rule and submits
    raw tasks directly, bypassing any protocol."""
    sess = ImpressSession(
        CampaignSpec(protocols=(), receptor_len=length, max_workers=4,
                     coalesce=False),
        payload=payload)
    ex = sess.executor
    if mode == "batched":
        ex.register_coalescable("generate_batch",
                                generate_batch_coalesce_rule(
                                    max_rows=n_pipelines,
                                    admission_window=0.0))
    elif mode == "continuous":
        ex.register_coalescable("generate_batch",
                                generate_batch_coalesce_rule(
                                    max_rows=n_pipelines,
                                    admission_window=0.25))
    rng = np.random.default_rng(0)
    tasks = make_tasks(mode, n_pipelines=n_pipelines, n_cand=n_cand,
                       length=length, rng=rng)

    log_start = len(gen_batch_log)
    if mode == "continuous":
        # no backlog: the first task is dequeued immediately, the rest
        # arrive while its admission window is open and join the batch
        t0 = time.perf_counter()
        for t in tasks:
            ex.submit(t)
        n_drain = len(tasks)
    else:
        gate = threading.Event()
        ex.register("blocker", lambda sm, p: gate.wait(timeout=60))
        ex.submit(Task(kind="blocker", payload={}))
        time.sleep(0.05)
        for t in tasks:
            ex.submit(t)
        t0 = time.perf_counter()
        gate.set()
        n_drain = len(tasks) + 1
    for _ in range(n_drain):
        if ex.drain(timeout=120) is None:
            raise RuntimeError(f"bench mode {mode}: executor stalled")
    dt = time.perf_counter() - t0
    stats = ex.coalesce_stats()
    stats["occupancy"] = [b["occupancy"] for b in gen_batch_log[log_start:]]
    sess.shutdown()
    return dt, stats


def run_decode_kernel(args, emit):
    """Occupancy sweep of ONE resident paged decode engine on one device:
    capacity (slots) is fixed at the sweep maximum, the number of live
    rows grows 8 -> 64, every row decodes ``--length`` tokens. This is
    the continuous-batching claim measured directly: admitting more rows
    into the in-flight batch must not slow the rows already decoding —
    per-sequence decode throughput holds flat (the fused step has a fixed
    dense shape, inactive slots are masked) while aggregate tokens/s
    rises with occupancy. One engine serves the whole sweep, so the
    trace counters double as the zero-recompile record."""
    from repro.models import protein as prot
    from repro.configs.registry import get_reduced

    cfg = get_reduced("progen-s")
    params = prot.init_progen(jax.random.PRNGKey(0), cfg)
    max_new, page_size = args.length, args.page_size
    sweep = (4, 8) if args.smoke else (8, 16, 32, 64)
    slots = sweep[-1]

    def specs(rows):
        rng = np.random.default_rng(7)
        return [dict(backbone=rng.normal(
                         size=(cfg.frontend_seq, 16)).astype(np.float32),
                     key=np.asarray(jax.random.PRNGKey(i), np.uint32),
                     length=max_new, tag=i) for i in range(rows)]

    eng = prot.PagedDecodeEngine(cfg, slots=slots, max_new=max_new,
                                 page_size=page_size)
    eng.run(params, 1.0, specs(2))               # warmup: compile admit/step
    records = {}
    # each sweep point is ~10-100 ms, so extra repeats are cheap — and the
    # min-of filter needs them when this runs right after the executor
    # benches, whose worker threads leave the machine briefly noisy
    reps = max(args.repeats, 5)
    for rows in sweep:
        t_admit, t_dec = min((_timed(eng, params, specs(rows))
                              for _ in range(reps)),
                             key=lambda t: t[0] + t[1])
        per_seq = max_new / t_dec                # tokens/s each row sees
        records[rows] = {"admit_seconds": t_admit, "decode_seconds": t_dec,
                         "tokens_per_sec_per_seq": per_seq,
                         "decode_tokens_per_sec": rows * max_new / t_dec}
        emit(f"decode-kernel-rows{rows},{rows * max_new / t_dec:.1f},"
             f"tok_s_per_seq={per_seq:.1f};admit_ms={t_admit * 1e3:.1f};"
             f"traces={eng.trace_counts['admit']}+{eng.trace_counts['step']}")
    lo, hi = sweep[0], sweep[-1]
    ratio = (records[hi]["tokens_per_sec_per_seq"]
             / records[lo]["tokens_per_sec_per_seq"])
    print(f"# per-seq decode throughput at occupancy {lo}->{hi} of "
          f"{slots} slots: {ratio:.2f}x "
          f"{'(flat-to-rising)' if ratio >= 0.9 else '(degrading)'}; "
          f"traces admit+step = {eng.trace_counts['admit']}+"
          f"{eng.trace_counts['step']} across the sweep (zero recompiles)")
    if args.json:
        import json as _json
        import os as _os
        try:
            from benchmarks._impress import write_bench_json
        except ImportError:
            from _impress import write_bench_json
        existing = {}
        if _os.path.exists(args.json):
            with open(args.json) as f:
                existing = _json.load(f)
        existing["decode_kernel"] = {
            "smoke": bool(args.smoke), "length": max_new,
            "page_size": page_size, "slots": slots,
            "rows": {str(r): records[r] for r in sweep},
            "per_seq_ratio_hi_vs_lo": ratio,
            "trace_counts": dict(eng.trace_counts),
        }
        write_bench_json(args.json, existing)
    return ratio


def _timed(eng, params, specs):
    """(admit_seconds, decode_seconds): admission/prefill is per-row work
    timed apart so the decode phase measures the steady-state fused step —
    the quantity continuous batching must hold flat as rows grow."""
    for s in specs:
        eng.submit(**s)
    t0 = time.perf_counter()
    eng._pump(params, 1.0)
    jax.block_until_ready(eng.state)
    t_admit = time.perf_counter() - t0
    t0 = time.perf_counter()
    while eng.active_slots():
        eng.step(params, 1.0)
    t_dec = time.perf_counter() - t0
    eng._results.clear()
    return t_admit, t_dec


def main(emit=print, argv=None):
    # Defaults model the steady state continuous batching targets: many
    # concurrent pipelines, each sampling a small candidate set per cycle
    # (so per-dispatch overhead dominates the per-pipeline baseline), with
    # enough pipelines to fill a whole batch bucket (occupancy 1.0).
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-candidates", type=int, default=2)
    ap.add_argument("--pipelines", type=int, default=16)
    ap.add_argument("--length", type=int, default=8)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes + single repeat (CI)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a machine-readable result record "
                         "(BENCH_generate.json)")
    ap.add_argument("--decode-kernel", action="store_true",
                    help="sweep the paged continuous-decode engine over "
                         "row counts instead of the three dispatch modes")
    ap.add_argument("--page-size", type=int, default=8,
                    help="KV page size for --decode-kernel")
    args = ap.parse_args(argv)
    if min(args.n_candidates, args.pipelines, args.length,
           args.repeats) < 1:
        ap.error("--n-candidates/--pipelines/--length/--repeats must be >= 1")
    if args.smoke:
        args.n_candidates, args.pipelines = 2, 4
        args.length, args.repeats = 8, 1
    if args.decode_kernel:
        return run_decode_kernel(args, emit)

    n_cand, n_pipe, length = args.n_candidates, args.pipelines, args.length
    payload = ProteinPayload(jax.random.PRNGKey(0), reduced=True,
                             length=length)
    total = n_pipe * n_cand

    results = {}
    for mode in MODES:
        run_mode(payload, mode, n_pipelines=n_pipe, n_cand=n_cand,
                 length=length)                     # warmup: compile cache
        best, stats = min(
            (run_mode(payload, mode, n_pipelines=n_pipe, n_cand=n_cand,
                      length=length)
             for _ in range(args.repeats)), key=lambda r: r[0])
        results[mode] = (total / best, stats)

    print("mode,seqs_per_sec,derived")
    base = results["per-pipeline"][0]
    for mode in MODES:
        sps, stats = results[mode]
        extra = [f"speedup={sps / base:.2f}x"]
        if mode != "per-pipeline":
            occ = stats["occupancy"]   # the best repeat's own dispatches
            extra.append(f"occupancy={np.mean(occ):.2f}" if occ
                         else "occupancy=n/a")
            extra.append(
                f"tasks_per_dispatch={stats['mean_tasks_per_dispatch']:.1f}")
        emit(f"{mode},{sps:.1f},{';'.join(extra)}")
    speedup = results["continuous"][0] / base
    print(f"# continuous vs per-pipeline at pipelines={n_pipe}: "
          f"{speedup:.2f}x {'(>= 3x target met)' if speedup >= 3 else ''}")
    if args.json:
        try:
            from benchmarks._impress import write_bench_json
        except ImportError:
            from _impress import write_bench_json
        cont_occ = results["continuous"][1]["occupancy"]
        write_bench_json(args.json, {
            "bench": "generate", "schema": 1, "smoke": bool(args.smoke),
            "n_candidates": n_cand, "pipelines": n_pipe, "length": length,
            "seqs_per_sec": {m: results[m][0] for m in MODES},
            "speedup_vs_per_pipeline": {
                m: results[m][0] / base for m in MODES},
            "occupancy": (float(np.mean(cont_occ)) if cont_occ else None),
        })
    return speedup


if __name__ == "__main__":
    from repro.session import enable_compilation_cache
    enable_compilation_cache()
    main()
