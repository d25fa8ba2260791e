"""Model-evolution benchmark: design throughput + design quality with
evolution on vs off (paper §V — "evaluate ... the models used to generate
data and train models").

Runs the same fixed-seed adaptive design workload twice through the
session facade:

  off   the seed protocol, no trainer attached (``evolution=False``)
  on    ``evolution=True``: a TrainerService feeds a replay buffer from
        accepted designs and finetunes the generator on idle devices
        (preemptible low-priority tasks); evolved params hot-swap mid-run

and measures (a) design makespan — trainer tasks must not slow design work
(they only soak idle devices and yield on preemption), and (b) the §V
acceptance signal: the post-finetune generator's mean log-likelihood over
the replay buffer improves on the version-0 generator (the model has
evolved toward the designs the protocol accepts).

``--long`` runs the long-horizon variant: more structures and cycles,
arriving as consecutive *waves* through one shared payload/ParamStore on a
simulated 4-device pilot. Evolved params persist across waves, so wave
N+1's generators sample from the versions wave N's finetunes published and
their accepted designs carry >v0 provenance — ``quality_by_version`` then
shows rows for generator versions > 0, the fitness-vs-version trend the
paper claims (closing the PR 3 ROADMAP follow-up: evolved generators need
enough remaining design cycles to produce accepted designs).

  PYTHONPATH=src python benchmarks/bench_evolution.py [--smoke|--long]
"""

from __future__ import annotations

import os
import sys

if "--long" in sys.argv:
    # simulate a small pilot (set BEFORE jax import): the long horizon
    # needs mid-run idle devices for the opportunistic trainer to soak
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=4")

import time         # noqa: E402

import numpy as np  # noqa: E402

from repro.models import protein as prot                         # noqa: E402
from repro.session import (CampaignSpec, ImpressSession,         # noqa: E402
                           ProtocolSpec)


def buffer_mean_ll(payload, params, buffer, n=32):
    """Mean generator log-likelihood over (up to n) replay-buffer designs
    under ``params`` — computed host-side, outside the middleware."""
    batch = buffer.sample(n, np.random.default_rng(0))
    if batch is None:
        return None
    bbs = batch["backbones"][:, :payload.gen_cfg.frontend_seq]
    lp = prot.progen_logprobs(params, bbs, batch["sequences"],
                              payload.gen_cfg)
    return float(np.mean(np.asarray(lp)))


def run_design(evolution, *, n_structures, n_cycles, n_candidates,
               receptor_len, steps, finetune_every, seed=0, timeout=600.0,
               n_waves=1):
    """One fixed-seed design workload, optionally arriving as ``n_waves``
    consecutive campaigns through ONE shared payload (long-horizon mode):
    the ParamStore persists across waves, so later waves sample from the
    generator versions earlier waves evolved."""
    payload = None
    params0 = None
    t0 = time.monotonic()
    design_dt = 0.0
    quality_rows = []       # (gen_version, fitness) across all waves
    trajectories = 0
    fitness_final = None
    n_preempted = 0
    evo = None
    buffer = None
    for wave in range(n_waves):
        spec = CampaignSpec(
            structures=n_structures, receptor_len=receptor_len,
            peptide_len=5,
            protocols=(ProtocolSpec("im-rp", n_candidates=n_candidates,
                                    n_cycles=n_cycles,
                                    max_sub_pipelines=2),),
            # same seed every wave: each wave designs the same structures
            # with the same decision streams, so v0 rows (wave 1) vs >v0
            # rows (later waves) compare generators, not structures
            evolution=evolution, finetune_every=finetune_every,
            finetune_steps=steps, finetune_lr=1e-3, min_designs=2,
            finetune_batch=8, seed=seed, max_workers=4,
            timeout=timeout)
        sess = ImpressSession(spec, payload=payload)
        if payload is None:
            payload = sess.payload
            params0 = payload.param_store.current()[1]  # version-0 snapshot
        tw = time.monotonic()
        rep = sess.run()
        dt = time.monotonic() - tw
        # design time ends at the last protocol decision: the run also
        # waits out a trailing finetune (busy()), which is idle-soak, not
        # design cost
        design_dt += max((e["t"] for e in rep.events if "cycle" in e),
                         default=tw + dt) - tw
        for p in sess.coordinator.pipelines.values():
            quality_rows += [(int(h.get("gen_version", 0)),
                              float(h["fitness"])) for h in p.history]
        trajectories += rep.trajectories
        fitness_by_cycle = [c["fitness_median"] for c in rep.cycles.values()]
        if fitness_final is not None:
            fitness_by_cycle.append(fitness_final)
        fitness_final = max(fitness_by_cycle, default=None)
        n_preempted += rep.executor["n_preempted"]
        if rep.evolution is not None:
            if evo is None:
                evo = dict(rep.evolution)
            else:   # accumulate counters across waves; latest for the rest
                prev = evo
                evo = dict(rep.evolution)
                for k in ("submitted", "completed", "preempted", "failed",
                          "steps_run", "device_seconds"):
                    evo[k] += prev[k]
                evo["finetunes"] = prev["finetunes"] + evo["finetunes"]
        buffer = sess.buffer
        sess.shutdown()
    by_v = {}
    for v, f in quality_rows:
        by_v.setdefault(v, []).append(f)
    out = {
        "seconds": time.monotonic() - t0,
        "design_seconds": design_dt,
        "trajectories": trajectories,
        "traj_per_sec": trajectories / max(design_dt, 1e-9),
        "fitness_final": fitness_final,
        "quality_by_version": {
            v: {"n": len(fs), "fitness_median": float(np.median(fs)),
                "fitness_mean": float(np.mean(fs))}
            for v, fs in sorted(by_v.items())},
        "n_preempted": n_preempted,
        "evolution": evo,
    }
    if evolution:
        out["mean_ll_v0"] = buffer_mean_ll(payload, params0, buffer)
        out["mean_ll_evolved"] = buffer_mean_ll(
            payload, payload.param_store.current()[1], buffer)
        out["final_version"] = payload.param_store.version
    return out


def _print_row(name, us_per_call, derived):
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


SIZES = {
    "smoke": dict(n_structures=2, n_cycles=2, n_candidates=3,
                  receptor_len=12, steps=4, finetune_every=2),
    "default": dict(n_structures=4, n_cycles=3, n_candidates=5,
                    receptor_len=16, steps=10, finetune_every=3),
    # long horizon: evolved generators need remaining cycles to produce
    # accepted designs before quality_by_version can show >v0 rows — the
    # structures arrive in waves through one persistent ParamStore
    "long": dict(n_structures=4, n_cycles=4, n_candidates=4,
                 receptor_len=12, steps=6, finetune_every=2,
                 timeout=1800.0, n_waves=3),
}


def main(emit=_print_row, smoke=False, long=False):
    """Rows follow the benchmarks.run convention:
    emit(name, us_per_call, derived)."""
    sizes = SIZES["smoke" if smoke else "long" if long else "default"]
    off = run_design(False, **sizes)
    on = run_design(True, **sizes)

    emit("evolution_off", off["design_seconds"] * 1e6,
         f"traj_per_sec={off['traj_per_sec']:.2f};"
         f"fitness_final={off['fitness_final']:.3f}")
    evo = on["evolution"]
    emit("evolution_on", on["design_seconds"] * 1e6,
         f"traj_per_sec={on['traj_per_sec']:.2f};"
         f"fitness_final={on['fitness_final']:.3f};"
         f"finetunes={evo['completed']};preempted={evo['preempted']};"
         f"trainer_util={evo['trainer_utilization']:.3f};"
         f"versions={on['final_version']}")
    gain = None
    if on.get("mean_ll_v0") is not None \
            and on.get("mean_ll_evolved") is not None:
        gain = on["mean_ll_evolved"] - on["mean_ll_v0"]
        emit("evolution_mean_ll", 0.0,
             f"v0={on['mean_ll_v0']:.3f};"
             f"evolved={on['mean_ll_evolved']:.3f};gain={gain:+.3f}")
    for v, q in sorted(on["quality_by_version"].items()):
        emit(f"evolution_quality_v{v}", 0.0,
             f"n={q['n']};fitness_median={q['fitness_median']:.3f};"
             f"fitness_mean={q['fitness_mean']:.3f}")
    n_evolved = sum(1 for v in on["quality_by_version"] if int(v) > 0)
    slowdown = on["design_seconds"] / max(off["design_seconds"], 1e-9)
    print(f"# evolution on/off design-time ratio {slowdown:.2f}x "
          f"(trainer runs on idle devices only); "
          f"mean-LL gain on replay buffer: "
          f"{'n/a' if gain is None else f'{gain:+.3f}'} "
          f"{'(improved)' if gain is not None and gain > 0 else ''}")
    if long:
        print(f"# long horizon: {n_evolved} generator version(s) > v0 with "
              f"accepted designs "
              f"{'(fitness-vs-version trend visible)' if n_evolved else ''}")
    return gain


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes (CI)")
    ap.add_argument("--long", action="store_true",
                    help="long horizon: enough cycles after each finetune "
                         "that quality_by_version shows >v0 rows")
    args = ap.parse_args()
    from repro.session import enable_compilation_cache
    enable_compilation_cache()
    print("name,us_per_call,derived")
    main(smoke=args.smoke, long=args.long)
