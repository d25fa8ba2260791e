#!/usr/bin/env python
"""Chip smoke: drive the design campaign and the gateway once on a TPU, at
the configured payload widths (``progen-s``, ``foldscore-s``,
``foldscore-m``), and check what comes out.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # only the multi-device path, 4 chips

One chip runs three phases in this one process:

``kernel``    the Pallas paged decode kernel at ``progen-s`` widths against
              its jnp twin (``paged_decode_ref``), page sizes 8 and 16.
``campaign``  one ``ImpressSession`` running im-rp (batched scoring and the
              paged decode kernel), cont-v, the three-stage binder protocol
              (backbone stage, ``foldscore-m`` fold stage) and model
              evolution, at mixed receptor lengths (96, 128).
``gateway``   ``GatewayService`` behind its HTTP server on a localhost
              port: two tenants each submit a campaign, poll their reports
              until COMPLETED, then read ``/metrics``.

``--chips 4`` runs the multi-device path and what it is compared with, and
nothing else: ``predict_batch`` on a four-device sub-mesh against one chip,
a finetune step sharded over four chips against one chip, and a short
campaign whose grants must span all four devices.

Any failed check, failed or retried task, quarantined task, non-finite
metric, or a platform other than ``tpu`` exits non-zero without the result
line. The last line of a passing run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The compile cache goes where ``repro.session.enable_compilation_cache``
puts it, so a second run loads what the first compiled.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import numbers
import os
import sys
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

KERNEL_TOL = 2e-2   # bf16 kernel vs twin: a few bf16 ulps at O(1) outputs
SHARD_TOL = 2e-2    # relative: four-device vs one-device scores and losses
UPDATE_COS = 0.99   # cosine of the four-device vs one-device param update


class SmokeFailure(Exception):
    """A check of the smoke failed."""


def check(ok, what: str):
    if not ok:
        raise SmokeFailure(what)


def log(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


def non_finite(obj, path="report"):
    """Paths of every non-finite number in a nested report."""
    import numpy as np
    if isinstance(obj, np.ndarray) and obj.dtype.kind == "f":
        return [] if np.isfinite(obj).all() else [path]
    if isinstance(obj, numbers.Real) and not isinstance(obj,
                                                        numbers.Integral):
        return [] if math.isfinite(obj) else [path]
    if isinstance(obj, dict):
        return [p for k, v in obj.items()
                for p in non_finite(v, f"{path}.{k}")]
    if isinstance(obj, (list, tuple)):
        return [p for i, v in enumerate(obj)
                for p in non_finite(v, f"{path}[{i}]")]
    return []


def check_clean(rep: dict, what: str):
    """No failed, retried or quarantined task; every metric finite."""
    ex = rep["executor"]
    check(ex["n_failed"] == 0, f"{what}: {ex['n_failed']} task(s) failed")
    check(ex["n_retried"] == 0, f"{what}: {ex['n_retried']} retries")
    dead = rep.get("resilience", {}).get("deadletter")
    check(not dead, f"{what}: quarantined {dead}")
    bad = non_finite(rep)
    check(not bad, f"{what}: non-finite metrics at {bad[:5]}")


def run_phase(name: str, fn, **kw) -> dict:
    """Run one phase under a ``CompileWatcher``: its result plus wall
    seconds, XLA compiles (persistent-cache loads included), compile
    seconds and cache hits, printed as one line."""
    from repro.obs import CompileWatcher, MetricsRegistry
    reg = MetricsRegistry()
    t0 = time.monotonic()
    with CompileWatcher(reg):
        out = fn(**kw)
    comp = reg.histogram("jax.compile_s", event="backend_compile_duration")
    hits = reg.histogram("jax.compile_s", event="cache_retrieval_time_sec")
    out = dict(out, wall_s=time.monotonic() - t0, compiles=comp.count,
               compile_s=comp.sum, cache_hits=hits.count)
    log(f"{name}: {json.dumps(out, sort_keys=True)}")
    return out


def record_paged(executor, payload) -> list:
    """Re-register ``generate_batch`` through a recorder of the decode mode
    every paged dispatch ran with (the coalesce rule, live admission
    included, stays as registered)."""
    modes = []

    def generate_batch(submesh, p):
        out = payload.generate_batch(submesh, p)
        if p.get("decode") == "paged":
            modes.append(out["batch"].get("decode"))
        return out

    executor.register("generate_batch", generate_batch)
    return modes


# -- one chip ----------------------------------------------------------------


def kernel_parity(*, reduced: bool, rows: int = 32, max_new: int = 144,
                  page_sizes=(8, 16), seed: int = 0) -> dict:
    """The paged decode kernel (compiled on a TPU, interpreted elsewhere)
    against its jnp twin on random pages at ``progen-s`` head layout;
    row 0 is an inactive slot and must come out exactly zero."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.registry import get_config, get_reduced
    from repro.kernels import paged_attention as pa
    from repro.kernels._compat import resolve_interpret
    cfg = (get_reduced if reduced else get_config)("progen-s")
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    g = cfg.n_heads // kv
    dt = jnp.dtype(cfg.compute_dtype)
    interpret = resolve_interpret(None)
    rng = np.random.default_rng(seed)
    errs = {}
    for ps in page_sizes:
        maxp = -(-(cfg.frontend_seq + max_new) // ps)
        pool = rows * maxp + 1
        q = jnp.asarray(rng.normal(size=(rows, kv, g, hd)), dt)
        kp = jnp.asarray(rng.normal(size=(pool, kv, ps, hd)), dt)
        vp = jnp.asarray(rng.normal(size=(pool, kv, ps, hd)), dt)
        bt = jnp.asarray(rng.permutation(pool)[:rows * maxp]
                         .reshape(rows, maxp), jnp.int32)
        lens = rng.integers(1, maxp * ps + 1, size=rows)
        lens[0] = 0
        args = (q, kp, vp, bt, jnp.asarray(lens, jnp.int32))
        kern = jax.jit(functools.partial(pa.paged_decode_bkgh, page_size=ps,
                                         interpret=interpret))
        twin = jax.jit(functools.partial(pa.paged_decode_ref, page_size=ps))
        got = np.asarray(kern(*args), np.float32)
        want = np.asarray(twin(*args), np.float32)
        check(got.shape == (rows, kv, g, hd), f"kernel shape {got.shape}")
        check(np.isfinite(got).all(), f"page_size {ps}: non-finite output")
        check(not got[0].any(), f"page_size {ps}: inactive row not zero")
        errs[ps] = float(np.abs(got - want).max())
        check(errs[ps] <= KERNEL_TOL, f"page_size {ps}: kernel vs twin "
              f"max |err| {errs[ps]} > {KERNEL_TOL}")
    return {"interpret": interpret, "rows": rows, "kv_heads": kv,
            "group": g, "head_dim": hd, "dtype": str(dt),
            "max_abs_err": errs}


def campaign(*, reduced: bool, receptor_len=(96, 128), peptide_len=16,
             structures=2, cycles=2, candidates=4,
             timeout: float = 900.0) -> dict:
    """im-rp (paged decode kernel), cont-v, binder and evolution in one
    ``ImpressSession``."""
    from repro.core import payload as payload_mod
    from repro.models.protein import PagedDecodeEngine
    from repro.session import CampaignSpec, ImpressSession, ProtocolSpec
    spec = CampaignSpec(
        structures=structures, receptor_len=tuple(receptor_len),
        peptide_len=peptide_len,
        protocols=(
            ProtocolSpec("im-rp", n_candidates=candidates, n_cycles=cycles,
                         score_batch=2, generate_batch_size=4,
                         decode_kernel=True, decode_slots=16),
            ProtocolSpec("cont-v", n_candidates=candidates, n_cycles=cycles),
            ProtocolSpec("binder", n_candidates=candidates, n_cycles=cycles,
                         score_batch=2)),
        evolution=True, finetune_every=2, min_designs=2, finetune_batch=4,
        finetune_steps=3, reduced=reduced, max_workers=4, seed=0,
        timeout=timeout)
    built0 = sum(len(v) for v in payload_mod.compile_log.values())
    with ImpressSession(spec) as sess:
        modes = record_paged(sess.executor, sess.payload)
        rep = sess.run().to_dict()
        engines = {k[0]: dict(e.trace_counts)
                   for k, e in sess.payload._cache.items()
                   if isinstance(e, PagedDecodeEngine)}
        accepted = sum(len(p.history)
                       for p in sess.coordinator.pipelines.values())
    check_clean(rep, "campaign")
    check(modes, "no paged decode dispatch ran")
    check(all(m == "paged" for m in modes),
          f"paged decode asked, dispatches ran {sorted(set(modes))}")
    evo = rep["evolution"]
    check(evo["completed"] >= 1 and evo["param_version"] >= 1,
          f"evolution published no new generator version: {evo}")
    ft = evo["finetunes"]
    return {"tasks_completed": rep["telemetry"]["counters"]["completed"],
            "accepted_designs": accepted,
            "trajectories": rep["trajectories"],
            "paged_dispatches": len(modes),
            "engine_trace_counts": engines,
            "generator_version": evo["param_version"],
            "finetune_loss_first": ft[0]["loss_first"],
            "finetune_loss_last": ft[-1]["loss_last"],
            "payload_executables": sum(
                len(v) for v in payload_mod.compile_log.values()) - built0,
            "length_buckets": rep["compile"]["length_buckets"],
            "persistent_cache_dir": rep["compile"]["persistent_cache_dir"]}


def _req(base, method, path, tok, body=None):
    data = json.dumps(body).encode() if body is not None else None
    r = urllib.request.Request(
        base + path, data=data, method=method,
        headers={"Authorization": f"Bearer {tok}",
                 "Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(r, timeout=60) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def gateway(*, reduced: bool, receptor_len=(96, 128), peptide_len=16,
            timeout: float = 600.0) -> dict:
    """Two tenants' binder campaigns over the gateway's HTTP API."""
    from repro.gateway import GatewayService, TenantQuota, make_server
    gw = GatewayService(max_workers=4, reduced=reduced,
                        payload_length=max(receptor_len),
                        quotas={"alice": TenantQuota(share=1.0),
                                "bob": TenantQuota(share=1.0)})
    gw.start()
    srv = make_server(gw, host="127.0.0.1", port=0,
                      tokens={"tok-a": "alice", "tok-b": "bob"})
    serving = threading.Thread(target=srv.serve_forever, daemon=True)
    serving.start()
    base = "http://%s:%d" % srv.server_address[:2]
    spec = {"structures": 2, "receptor_len": list(receptor_len),
            "peptide_len": peptide_len,
            "protocols": [{"kind": "binder", "n_cycles": 1,
                           "n_candidates": 4, "score_batch": 2}]}
    try:
        ids = {}
        for tok, seed in (("tok-a", 0), ("tok-b", 1)):
            status, body = _req(base, "POST", "/campaigns", tok,
                                dict(spec, seed=seed))
            check(status == 201, f"submit as {tok}: {status} {body}")
            ids[tok] = body["id"]
        deadline = time.monotonic() + timeout
        while True:
            reports = {tok: _req(base, "GET", f"/campaigns/{cid}/report",
                                 tok)[1] for tok, cid in ids.items()}
            states = {tok: r.get("state") for tok, r in reports.items()}
            if all(s != "RUNNING" for s in states.values()) \
                    or time.monotonic() > deadline:
                break
            time.sleep(0.5)
        status, metrics = _req(base, "GET", "/metrics", "tok-a")
        check(status == 200, f"/metrics: {status}")
    finally:
        srv.shutdown()
        srv.server_close()
        serving.join(timeout=10)
        gw.shutdown()
    for tok, r in reports.items():
        check(r.get("state") == "COMPLETED" and r.get("trajectories", 0) > 0,
              f"{tok} campaign ended {r.get('state')} "
              f"with {r.get('trajectories')} trajectories")
        dead = r.get("resilience", {}).get("deadletter")
        check(not dead, f"{tok} campaign quarantined {dead}")
    snap = metrics["metrics"]
    failed = {k: v for k, v in snap.items()
              if k.startswith(("tasks.failed", "tasks.retried")) and v}
    check(not failed, f"gateway tasks failed or retried: {failed}")
    bad = non_finite(metrics, "metrics") + non_finite(reports, "reports")
    check(not bad, f"gateway: non-finite metrics at {bad[:5]}")
    done = {k[len("tasks.completed{kind="):-1]: int(v)
            for k, v in snap.items() if k.startswith("tasks.completed{")}
    return {"states": states, "tasks_completed": done,
            "trajectories": {t: r["trajectories"]
                             for t, r in reports.items()},
            "cross_tenant_dispatches": metrics["coalesce"].get(
                "cross_tenant", {}).get("dispatches", 0)}


# -- four chips --------------------------------------------------------------


def _on(tree, device) -> bool:
    import jax
    return all(leaf.devices() == {device}
               for leaf in jax.tree_util.tree_leaves(tree))


def sharded_predict(*, reduced: bool, devices, rows: int = 8,
                    receptor_len: int = 128, peptide_len: int = 16) -> dict:
    """``predict_batch`` on the ``foldscore-m`` scorer, masked mixed-length
    form, on a sub-mesh over every device against the same batch on one."""
    import jax
    import numpy as np
    from repro.core import ProteinPayload
    from repro.runtime import DeviceAllocator
    rng = np.random.default_rng(0)
    L = receptor_len + peptide_len
    splits = rng.integers(receptor_len // 2, receptor_len + 1, size=rows)
    payload = {"sequences": rng.integers(1, 21, size=(rows, L)),
               "target": rng.normal(size=16).astype(np.float32),
               "receptor_len": receptor_len,
               "seq_lens": (splits + peptide_len).astype(np.int32),
               "chain_splits": splits.astype(np.int32),
               "params": "multimer"}
    pp = ProteinPayload(jax.random.PRNGKey(0), reduced=reduced, length=L)
    pp.add_scorer("multimer")
    alloc = DeviceAllocator(devices)
    out = {}
    for n in (len(devices), 1):
        sub = alloc.request(n)
        res = pp.predict_batch(sub, payload)
        check(res["batch"]["devices"] == n,
              f"predict_batch split over {res['batch']['devices']} of {n}")
        for d in sub.devices.flat:
            copy = pp._cache.get((("fold", "multimer"), "params", d.id))
            check(copy is not None and _on(copy, d),
                  f"scorer params not placed on device {d.id}")
        out[n] = res["rows"]
        alloc.release(sub)
    worst = 0.0
    for a, b in zip(out[len(devices)], out[1]):
        for k in ("plddt", "ptm", "pae"):
            check(math.isfinite(a[k]), f"non-finite {k}")
            worst = max(worst, abs(a[k] - b[k]) / max(1.0, abs(b[k])))
    check(worst <= SHARD_TOL, f"predict_batch {len(devices)} vs 1 device: "
          f"relative diff {worst} > {SHARD_TOL}")
    return {"rows": rows, "length": L, "max_rel_diff": worst,
            "devices": [d.id for d in devices]}


def sharded_finetune(*, reduced: bool, devices, batch: int = 8,
                     seq_len: int = 96, steps: int = 2) -> dict:
    """``FinetunePayload`` steps sharded data-parallel over every device
    against the same steps on one: losses and the param update agree."""
    import jax
    import numpy as np
    from repro.core import ProteinPayload
    from repro.core.payload import FinetunePayload
    from repro.runtime import DeviceAllocator
    rng = np.random.default_rng(1)
    payload = {"backbones": rng.normal(size=(batch, seq_len, 16)
                                       ).astype(np.float32),
               "sequences": rng.integers(1, 21, size=(batch, seq_len)
                                         ).astype(np.int32),
               "weights": rng.uniform(0.5, 1.5, size=batch
                                      ).astype(np.float32),
               "steps": steps}
    runs = {}
    for n in (len(devices), 1):
        pp = ProteinPayload(jax.random.PRNGKey(0), reduced=reduced)
        base = pp.param_store.current()[1]
        sub = DeviceAllocator(devices).request(n)
        info = FinetunePayload(pp, lr=1e-3, steps=steps).finetune(sub,
                                                                  payload)
        check(info["n_devices"] == n and info["new_version"] == 1,
              f"finetune on {n} device(s): {info}")
        delta = jax.tree_util.tree_map(lambda new, old: np.asarray(
            new, np.float32) - np.asarray(old, np.float32),
            pp.param_store.current()[1], base)
        runs[n] = (info, np.concatenate(
            [d.ravel() for d in jax.tree_util.tree_leaves(delta)]))
    (wide, dw), (one, d1) = runs[len(devices)], runs[1]
    rel = max(abs(wide[k] - one[k]) / max(1.0, abs(one[k]))
              for k in ("loss_first", "loss_last"))
    cos = float(dw @ d1 / max(np.linalg.norm(dw) * np.linalg.norm(d1),
                              1e-30))
    check(all(math.isfinite(v) for v in (wide["loss_first"],
                                         wide["loss_last"], cos)),
          "non-finite finetune result")
    check(rel <= SHARD_TOL, f"finetune loss {len(devices)} vs 1 device: "
          f"relative diff {rel} > {SHARD_TOL}")
    check(cos >= UPDATE_COS, f"finetune update cosine {cos} < {UPDATE_COS}")
    return {"batch": batch, "seq_len": seq_len, "steps": steps,
            "loss_first": wide["loss_first"], "loss_last": wide["loss_last"],
            "loss_max_rel_diff": rel, "update_cosine": cos}


def spanning_campaign(*, reduced: bool, devices, receptor_len: int = 96,
                      peptide_len: int = 16, timeout: float = 900.0) -> dict:
    """A short im-rp campaign over every device: batched generate and
    scoring grants must between them cover every device id."""
    from repro.session import CampaignSpec, ImpressSession, ProtocolSpec
    spec = CampaignSpec(
        structures=4, receptor_len=receptor_len, peptide_len=peptide_len,
        protocols=(ProtocolSpec("im-rp", n_candidates=4, n_cycles=1,
                                score_batch=4, generate_batch_size=4),),
        reduced=reduced, max_workers=4, seed=0, timeout=timeout)
    seen = set()
    with ImpressSession(spec, devices=devices) as sess:
        request = sess.allocator.request

        def recording(*a, **kw):
            sub = request(*a, **kw)
            if sub is not None:
                seen.update(d.id for d in sub.devices.flat)
            return sub

        sess.allocator.request = recording
        rep = sess.run().to_dict()
    check_clean(rep, "spanning campaign")
    want = {d.id for d in devices}
    check(seen == want, f"grants covered devices {sorted(seen)}, "
          f"want {sorted(want)}")
    return {"granted_device_ids": sorted(seen),
            "tasks_completed": rep["telemetry"]["counters"]["completed"],
            "trajectories": rep["trajectories"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Run the campaign and the gateway once on a TPU.")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the multi-device path, on four chips")
    args = ap.parse_args(argv)
    import jax
    from repro.kernels._compat import resolve_interpret
    from repro.session import enable_compilation_cache
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"[chip_smoke] --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    try:
        check(resolve_interpret(None) is False,
              "Pallas kernels would run interpreted on the TPU")
        cache = enable_compilation_cache()
        log(f"device {dev.device_kind} x{len(devices)}, "
            f"compile cache {cache}")
        t0 = time.monotonic()
        if args.chips == 4:
            devs = devices[:4]
            run_phase("sharded_predict", sharded_predict, reduced=False,
                      devices=devs)
            run_phase("sharded_finetune", sharded_finetune, reduced=False,
                      devices=devs)
            run_phase("spanning_campaign", spanning_campaign, reduced=False,
                      devices=devs)
        else:
            run_phase("kernel", kernel_parity, reduced=False)
            run_phase("campaign", campaign, reduced=False)
            run_phase("gateway", gateway, reduced=False)
        log(f"all phases passed in {time.monotonic() - t0:.1f}s")
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
