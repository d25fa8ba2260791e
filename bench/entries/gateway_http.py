"""Entry kind ``gateway_http``: the resident multi-tenant gateway behind
its HTTP API on a localhost port, built as ``launch/serve.py --gateway``
builds it, under open-loop Poisson arrivals from ``bench/client.py`` in a
child process.

Each campaign is timed from its due time to its last design event on the
machine's monotonic clock (the coordinator's own event stamps), not from
when anyone polled. The campaigns due in the window are followed to the
end within ``drain_s`` of the window's close; one that fails, gets an HTTP
error or misses the drain is failed and counts as missing any limit (its
latency is taken as the time from its due time to the drain's end).

End-to-end: ``campaign_p95_s`` and ``designs_per_s`` (designs of the
window's campaigns over the time from the window's start to the last
one's completion). ``attempted`` / ``failed`` count campaigns.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from bench import mix, structures, warm

DESIGN_EVENTS = ("accepted", "completed")
DECISION_EVENTS = ("accepted", "completed", "reselect", "pruned")


def setup(run):
    from repro.data import protein_design_tasks
    from repro.gateway import GatewayService, TenantQuota, make_server
    from repro.runtime.allocator import LENGTH_BUCKETS, bucket_len
    a = run.traffic["arrivals"]
    first = mix.arrivals(run.traffic, run.seed, run.seconds)[:1]
    for arrival in first:
        structures.check_program(protein_design_tasks, arrival["body"])
    tenants = sorted(a["tenant_shares"])
    gw = GatewayService(devices=run.devices, max_workers=a["max_workers"],
                        payload=run.payload,
                        quotas={t: TenantQuota(share=1.0) for t in tenants})
    add = gw.coordinator.add_protocol

    def add_protocol(proto, *args, **kw):
        return add(run.recorder.wrap(proto), *args, **kw)

    gw.coordinator.add_protocol = add_protocol
    gw.start()
    srv = make_server(gw, host="127.0.0.1", port=0,
                      tokens={f"tok-{t}": t for t in tenants})
    serving = threading.Thread(target=srv.serve_forever, daemon=True)
    serving.start()
    run.extra.update(gw=gw, srv=srv, serving=serving)

    spec = a["spec"]
    pep, lens = int(spec["peptide_len"]), [int(v) for v in a["receptor_lens"]]
    ps = spec["protocols"][0]
    subs = warm.submeshes(run.devices)
    rows = warm.row_buckets()
    gen_ns = fold_ns = None
    for m in run.config["models"].values():
        if m["kind"] == "generator":
            gen_ns = m["param_set"]
        else:
            fold_ns = m["param_set"]
    warm.backbones(run.payload, subs, rows=rows,
                   widths=sorted({v + pep for v in lens}),
                   m=int(a["backbone"]["m"]),
                   sigma=float(a["backbone"]["sigma"]))
    warm.dense_generator(
        run.payload, subs, rows=rows, n=int(ps["n_candidates"]),
        prefix=int(run.cfgs["generator"].frontend_seq), params=gen_ns,
        lengths=sorted({bucket_len(v, LENGTH_BUCKETS) for v in lens}))
    warm.scorer(run.payload, subs, rows=rows, peptide_len=pep,
                params=fold_ns,
                lengths=sorted({bucket_len(v + pep, LENGTH_BUCKETS)
                                for v in lens}))

    host, port = srv.server_address[:2]
    client = subprocess.Popen(
        [sys.executable, os.path.join(run.bench_dir, "client.py"),
         "--traffic", json.dumps(run.traffic), "--seed", str(run.seed),
         "--seconds", repr(run.seconds), "--url", f"http://{host}:{port}"],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    ready = json.loads(client.stdout.readline())
    run.extra.update(client=client, offered=int(ready["ready"]))


def _campaign_end(gw, cid):
    """(state, completion time, designs) of one campaign from the
    coordinator's own event stamps."""
    rec = gw._campaigns.get(cid)
    events = [e for e in list(gw.coordinator.events)
              if str(e.get("protocol", "")).startswith(cid + "/")]
    ends = [e["t"] for e in events if e.get("event") in DECISION_EVENTS]
    designs = sum(e.get("event") in DESIGN_EVENTS for e in events)
    state = rec.state.value if rec is not None else "MISSING"
    return state, (max(ends) if ends else None), designs


def window(run):
    a = run.traffic["arrivals"]
    client, gw = run.extra["client"], run.extra["gw"]
    client.stdin.write(f"{run.t0!r}\n")
    client.stdin.flush()
    lines = client.stdout.read().splitlines()
    client.wait(timeout=60)
    records = [json.loads(x) for x in lines if x.strip()]
    deadline = run.t1 + float(a["drain_s"])
    ids = [r["id"] for r in records if r["status"] == 201]
    while time.monotonic() < deadline:
        with gw._lock:
            gw._refresh_states()
            states = [gw._campaigns[c].state.value for c in ids]
        if all(s != "RUNNING" for s in states):
            break
        time.sleep(0.05)
    run.extra["records"] = records
    run.extra["drain_end"] = min(time.monotonic(), deadline)
    run.registries.append(gw.telemetry.metrics)


def results(run):
    gw = run.extra["gw"]
    drain_end = run.extra["drain_end"]
    lat, late, submit, designs, ends, failed = [], [], [], 0, [], 0
    for r in run.extra["records"]:
        late.append(r["sent"] - r["due"])
        submit.append(r["answered"] - r["sent"])
        ok = r["status"] == 201
        if ok:
            state, end, n = _campaign_end(gw, r["id"])
            ok = state == "COMPLETED" and end is not None
        if ok:
            lat.append(end - r["due"])
            ends.append(end)
            designs += n
        else:
            failed += 1
            lat.append(drain_end - r["due"])
    run.extra.update(submit_s=submit, late_s=late)
    print(f"[bench] generator lateness: p50 "
          f"{float(np.percentile(late, 50)) if late else 0.0!r} s, max "
          f"{max(late, default=0.0)!r} s over {len(late)} arrivals",
          file=sys.stderr, flush=True)
    if ends:
        run.extra["t_last"] = max(ends)
    span = (max(ends) - run.t0) if ends else run.seconds
    return {
        "campaign_p95_s": (float(np.percentile(lat, 95)) if lat
                           else float(run.seconds)),
        "designs_per_s": designs / span,
        "setup_s": run.setup_s,
        "attempted": len(run.extra["records"]),
        "failed": failed,
    }


def teardown(run):
    srv, gw = run.extra["srv"], run.extra["gw"]
    srv.shutdown()
    srv.server_close()
    run.extra["serving"].join(timeout=10)
    gw.shutdown()
