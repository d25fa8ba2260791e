"""Shared IMPRESS experiment runner for the paper-table benchmarks.

Runs the adaptive (IM-RP) and control (CONT-V) protocols with real (reduced)
ProGen/FoldScore payloads on the available devices through the session
facade, mirroring the paper's experimental setup (§III): same starting
structures, same cycle budget; the control picks candidates at random,
never compares, never prunes, executes strictly sequentially (the "cont-v"
protocol kind carries ``max_inflight=1``).
"""

from __future__ import annotations

import json
import time
from functools import lru_cache

import jax

from repro.core.payload import clear_compile_log, compile_log
from repro.session import CampaignSpec, ImpressSession, ProtocolSpec


def write_bench_json(path: str, record: dict):
    """Persist a benchmark's machine-readable result record (the repo's
    perf trajectory across PRs — ``benchmarks/run.py`` emits
    ``BENCH_scoring.json`` / ``BENCH_generate.json``)."""
    record = dict(record, unix_time=time.time(),
                  n_devices=len(jax.devices()))
    with open(path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {path}")


def run_impress(adaptive: bool, *, n_structures=4, n_cycles=4,
                n_candidates=6, receptor_len=24, seed=0,
                max_sub_pipelines=8, reduced=True, timeout=900.0,
                score_batch=0, generate_batch_size=0):
    spec = CampaignSpec(
        structures=n_structures, receptor_len=receptor_len, peptide_len=6,
        protocols=(ProtocolSpec(
            "im-rp" if adaptive else "cont-v",
            n_candidates=n_candidates, n_cycles=n_cycles,
            max_sub_pipelines=max_sub_pipelines,
            score_batch=score_batch,
            generate_batch_size=generate_batch_size,
            gen_devices=min(2, len(jax.devices())), predict_devices=1),),
        seed=seed, reduced=reduced, max_workers=4, timeout=timeout)
    sess = ImpressSession(spec)
    clear_compile_log()
    report = sess.run().to_dict()
    report["bootstrap_s"] = sess.bootstrap_s
    report["exec_setup_s"] = sum(sum(v) for v in compile_log.values())
    report["timeline"] = sess.allocator.busy_timeline()
    sess.shutdown()
    return report


@lru_cache(maxsize=None)
def cached_run(adaptive: bool, n_structures: int, n_cycles: int,
               n_candidates: int):
    return run_impress(adaptive, n_structures=n_structures,
                       n_cycles=n_cycles, n_candidates=n_candidates)


def quality_delta(report):
    """Net first->last cycle change of each metric's median (paper Table I)."""
    cycles = report["cycles"]
    if not cycles:
        return {}
    keys = sorted(cycles)
    first, last = cycles[keys[0]], cycles[keys[-1]]
    return {
        "ptm_net": last["ptm_median"] - first["ptm_median"],
        "plddt_net": last["plddt_median"] - first["plddt_median"],
        "pae_net": last["pae_median"] - first["pae_median"],
    }
