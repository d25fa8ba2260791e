"""Entry kind ``session_loop``: a batch user's closed loop of design
campaigns through ``ImpressSession``, one campaign at a time, back to
back, all sharing the one warm ``ProteinPayload`` (the session's
``payload=`` injection). The campaign running when the window closes is
stopped with ``run(timeout=remaining)``; designs count up to the close.

End-to-end: ``designs_per_s`` (accepted designs in the window over its
length) and ``cycle_p95_s`` (95th percentile over those designs of the
time since the pipeline's previous accepted design, or its creation).
``attempted`` / ``failed`` count the campaigns' tasks.
"""

from __future__ import annotations

import time

import numpy as np

from bench import mix, structures, warm


def _spec(run, index):
    from repro.session import CampaignSpec, ProtocolSpec
    d = mix.campaign_spec(run.traffic, run.seed, index, run.chips)
    protos = tuple(ProtocolSpec(**p) for p in d.pop("protocols"))
    return CampaignSpec(protocols=protos, device_budget=run.chips,
                        reduced=run.config.get("program_preset")
                        == "reduced", **d)


def setup(run):
    from repro.data import protein_design_tasks
    from repro.runtime.allocator import bucket_len
    from repro.session import campaign_length_buckets
    structures.check_program(protein_design_tasks, mix.campaign_spec(
        run.traffic, run.seed, 0, run.chips))
    spec = _spec(run, 0)
    ps = spec.protocols[0]
    buckets = campaign_length_buckets(spec)
    run.payload.length_buckets = buckets
    lens = [int(v) for v in spec.receptor_len]
    subs = warm.submeshes(run.devices)
    prefix = int(run.cfgs["generator"].frontend_seq)
    warm.paged_generator(
        run.payload, subs, n=ps.n_candidates, slots=ps.decode_slots,
        prefix=prefix, temperature=ps.temperature,
        lengths=sorted({bucket_len(v, buckets) for v in lens}))
    warm.scorer(run.payload, subs, rows=warm.row_buckets(),
                peptide_len=spec.peptide_len,
                lengths=sorted({bucket_len(v + spec.peptide_len, buckets)
                                for v in lens}))
    run.extra["stats"] = []


def window(run):
    from repro.session import ImpressSession
    index = 0
    while True:
        left = run.t1 - time.monotonic()
        if left <= 0:
            break
        with ImpressSession(_spec(run, index), payload=run.payload,
                            devices=run.devices) as sess:
            for proto in sess.protocols.values():
                run.recorder.wrap(proto)
            sess.run(timeout=left)
        run.registries.append(sess.telemetry.metrics)
        run.extra["stats"].append(sess.executor.stats())
        index += 1
    run.extra["campaigns"] = index


def results(run):
    cycles = run.recorder.designs(run.t0, run.t1)
    stats = run.extra["stats"]
    return {
        "designs_per_s": len(cycles) / run.seconds,
        "cycle_p95_s": (float(np.percentile(cycles, 95)) if cycles
                        else float(run.seconds)),
        "setup_s": run.setup_s,
        "attempted": sum(s["n_tasks"] for s in stats),
        "failed": sum(s["n_failed"] for s in stats),
    }


def teardown(run):
    pass
