"""Serving drivers: LM token serving and design-campaign serving.

LM mode (default) prefills a batch of prompts, then decodes:

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \\
      --batch 4 --prompt-len 32 --gen 16

Campaign mode runs a declarative design campaign through the
``ImpressSession`` facade — protocol kinds are spec-addressable, so one
flag serves IM-RP, the CONT-V control, the multi-objective demo, or any
mix of them concurrently on one pilot:

  PYTHONPATH=src python -m repro.launch.serve --campaign im-rp,cont-v \\
      --structures 4 --cycles 3 [--evolution] [--reduced]

Ctrl-C in campaign mode is graceful: the campaign is checkpointed (to
``--checkpoint-out``) and the partial report printed before exiting, so
an interrupted run never loses its accepted designs.

Gateway mode starts the persistent multi-tenant service instead — one
resident runtime, campaigns submitted over a JSON HTTP API, co-tenant
same-bucket batches fused across campaigns:

  PYTHONPATH=src python -m repro.launch.serve --gateway --port 8642 \\
      [--tokens tok-a=alice,tok-b=bob] [--quota alice=2.0:4] [--reduced]

Every mode runs the configured model widths unless ``--reduced`` asks for
the reduced-scale models, and keeps XLA's persistent compilation cache
where ``repro.session.enable_compilation_cache`` puts it.

The CLI is deliberately thin: every behavior lives in
``repro.gateway.GatewayService``; this file only parses flags, prints
curl examples, and turns Ctrl-C into a graceful drain (every live
campaign checkpointed to ``--checkpoint-dir``).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_reduced
from repro.models import lm


def serve_batch(cfg, *, batch, prompt_len, gen, temperature=0.0, seed=0):
    params = lm.init_lm(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 1)
    prompts = jax.random.randint(key, (batch, prompt_len), 1, cfg.vocab_size)
    b = {"inputs": prompts}
    if cfg.frontend == "vision_patches":
        b["patches"] = 0.02 * jax.random.normal(
            key, (batch, cfg.frontend_seq, cfg.d_model))
    elif cfg.frontend == "audio_frames":
        b["frames"] = 0.02 * jax.random.normal(
            key, (batch, cfg.frontend_seq, cfg.d_model))

    cache_len = prompt_len + gen + (
        cfg.frontend_seq if cfg.frontend == "vision_patches" else 0)
    decode = jax.jit(lambda p, c, tok, t: lm.decode_step(p, c, tok, t, cfg))

    t0 = time.time()
    logits, caches, t = lm.prefill(params, b, cfg, cache_len=cache_len)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    tok = jnp.argmax(logits, -1)[:, None]
    out = [tok]
    t0 = time.time()
    for i in range(gen - 1):
        logits, caches = decode(params, caches, tok, t)
        tok = jnp.argmax(logits, -1)[:, None]
        out.append(tok)
        t = t + 1
    jax.block_until_ready(tok)
    t_decode = time.time() - t0
    toks = jnp.concatenate(out, axis=1)
    return {
        "tokens": toks,
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        "decode_tok_s": batch * (gen - 1) / max(t_decode, 1e-9),
        "prefill_tok_s": batch * prompt_len / max(t_prefill, 1e-9),
    }


def serve_campaign(*, protocols, structures, cycles, candidates,
                   receptor_len, evolution, reduced=False, timeout=600.0,
                   trace_dir=None, metrics_every=0.0,
                   checkpoint_out="impress-checkpoint.json"):
    """Run a design campaign through the session facade and return its
    versioned report. ``trace_dir`` enables span tracing (Perfetto JSON +
    metrics snapshot written there); ``metrics_every`` > 0 prints a live
    metrics snapshot line every that-many seconds while the campaign runs.

    KeyboardInterrupt is a graceful exit, not a crash: the campaign is
    checkpointed to ``checkpoint_out`` and the partial report over
    whatever completed so far is returned — previously Ctrl-C discarded
    both, losing every accepted design of a long pilot."""
    import json
    import threading

    from repro.session import CampaignSpec, ImpressSession, ProtocolSpec
    spec = CampaignSpec(
        structures=structures, receptor_len=receptor_len,
        protocols=tuple(ProtocolSpec(kind, n_candidates=candidates,
                                     n_cycles=cycles)
                        for kind in protocols),
        evolution=evolution, reduced=reduced, timeout=timeout,
        trace_dir=trace_dir)
    with ImpressSession(spec) as session:
        stop = threading.Event()
        if metrics_every > 0:
            def _live():
                while not stop.wait(metrics_every):
                    snap = session.metrics_snapshot()
                    done = sum(v for k, v in snap.items()
                               if k.startswith("tasks.completed"))
                    depth = sum(v for k, v in snap.items()
                                if k.startswith("queue.depth"))
                    free = snap.get("devices.free", 0)
                    print(f"[serve] live: {int(done)} tasks done, "
                          f"queue depth {int(depth)}, "
                          f"{int(free)} devices free", flush=True)
            threading.Thread(target=_live, daemon=True).start()
        try:
            return session.run()
        except KeyboardInterrupt:
            if checkpoint_out:
                with open(checkpoint_out, "w") as f:
                    json.dump(session.checkpoint(), f)
                print(f"[serve] interrupted: campaign checkpointed to "
                      f"{checkpoint_out} (resume via "
                      f"ImpressSession.from_checkpoint)", flush=True)
            return session.partial_report()
        finally:
            stop.set()


def serve_gateway(*, host="127.0.0.1", port=8642, tokens=None, quotas=None,
                  max_workers=8, reduced=False, payload_length=64,
                  trace_dir=None, checkpoint_dir=None):
    """Start the persistent gateway + its HTTP front-end and block until
    Ctrl-C, which drains gracefully: every live campaign is checkpointed
    (written to ``checkpoint_dir`` when given) before the process exits."""
    from repro.gateway import GatewayService, make_server
    gw = GatewayService(max_workers=max_workers, reduced=reduced,
                        payload_length=payload_length, quotas=quotas,
                        trace_dir=trace_dir, checkpoint_dir=checkpoint_dir)
    gw.start()
    srv = make_server(gw, host=host, port=port, tokens=tokens)
    bound_host, bound_port = srv.server_address[:2]
    base = f"http://{bound_host}:{bound_port}"
    auth = (f' -H "Authorization: Bearer {next(iter(tokens))}"'
            if tokens else "")
    print(f"[serve] gateway listening on {base}", flush=True)
    print(f"[serve]   submit:  curl{auth} -X POST {base}/campaigns "
          "-d '{\"structures\": 2, \"receptor_len\": [24, 32], "
          "\"protocols\": [{\"kind\": \"binder\"}]}'", flush=True)
    print(f"[serve]   report:  curl{auth} {base}/campaigns/c0000/report",
          flush=True)
    print(f"[serve]   metrics: curl{auth} {base}/metrics", flush=True)
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        srv.shutdown()
        checkpoints = gw.shutdown()
        if checkpoints:
            where = (f" to {checkpoint_dir}" if checkpoint_dir
                     else " (pass --checkpoint-dir to persist)")
            print(f"[serve] checkpointed {len(checkpoints)} live "
                  f"campaign(s){where}: {sorted(checkpoints)}", flush=True)
        print("[serve] gateway stopped", flush=True)


def _parse_kv(arg, what):
    """Parse ``a=x,b=y`` flags (``--tokens``/``--quota``) into a dict."""
    out = {}
    for part in filter(None, (arg or "").split(",")):
        if "=" not in part:
            raise SystemExit(f"[serve] bad --{what} entry {part!r} "
                             f"(want key=value[,key=value...])")
        k, v = part.split("=", 1)
        out[k.strip()] = v.strip()
    return out or None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--campaign", default=None, metavar="KINDS",
                    help="serve a design campaign instead: comma-separated "
                         "protocol kinds (e.g. im-rp,cont-v)")
    ap.add_argument("--structures", type=int, default=2)
    ap.add_argument("--cycles", type=int, default=3)
    ap.add_argument("--candidates", type=int, default=5)
    ap.add_argument("--receptor-len", type=int, default=20)
    ap.add_argument("--evolution", action="store_true",
                    help="campaign mode: online model evolution (§V)")
    ap.add_argument("--trace-dir", default=None,
                    help="campaign mode: enable span tracing and write "
                         "Perfetto trace.json + metrics.json here")
    ap.add_argument("--metrics-every", type=float, default=0.0,
                    help="campaign mode: print a live metrics snapshot "
                         "every N seconds while the campaign runs")
    ap.add_argument("--checkpoint-out", default="impress-checkpoint.json",
                    help="campaign mode: where Ctrl-C writes the campaign "
                         "checkpoint ('' disables)")
    ap.add_argument("--gateway", action="store_true",
                    help="serve the persistent multi-tenant gateway "
                         "(JSON HTTP API) instead")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8642)
    ap.add_argument("--tokens", default=None, metavar="TOK=TENANT,...",
                    help="gateway mode: bearer-token auth table; omit for "
                         "open single-user mode")
    ap.add_argument("--quota", default=None, metavar="TENANT=SHARE[:CAP],..",
                    help="gateway mode: per-tenant fair share and optional "
                         "hard device cap (e.g. alice=2.0:4,bob=1.0)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="gateway mode: Ctrl-C writes every live "
                         "campaign's checkpoint here")
    args = ap.parse_args()
    from repro.session import enable_compilation_cache
    enable_compilation_cache()
    if args.gateway:
        from repro.gateway import TenantQuota
        quotas = None
        if args.quota:
            quotas = {}
            for tenant, v in (_parse_kv(args.quota, "quota") or {}).items():
                share, _, cap = v.partition(":")
                quotas[tenant] = TenantQuota(
                    share=float(share or 1.0),
                    max_devices=int(cap) if cap else None)
        serve_gateway(host=args.host, port=args.port,
                      tokens=_parse_kv(args.tokens, "tokens"),
                      quotas=quotas, reduced=args.reduced,
                      trace_dir=args.trace_dir,
                      checkpoint_dir=args.checkpoint_dir)
        return
    if args.campaign:
        rep = serve_campaign(protocols=args.campaign.split(","),
                             structures=args.structures, cycles=args.cycles,
                             candidates=args.candidates,
                             receptor_len=args.receptor_len,
                             evolution=args.evolution,
                             reduced=args.reduced,
                             trace_dir=args.trace_dir,
                             metrics_every=args.metrics_every,
                             checkpoint_out=args.checkpoint_out)
        print(f"[serve] campaign schema v{rep.schema_version}: "
              f"{rep.trajectories} trajectories in {rep.makespan_s:.1f}s, "
              f"utilization {100 * rep.utilization:.0f}%")
        for name, p in rep.protocols.items():
            print(f"[serve]   {name}: {p['n_pipelines']} pipelines "
                  f"(+{p['n_sub_pipelines']} subs), "
                  f"{p['trajectories']} trajectories")
        tel = rep.raw.get("telemetry", {})
        if tel.get("trace_path"):
            print(f"[serve] trace: {tel['trace_path']} "
                  f"(load in ui.perfetto.dev)")
        return
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    r = serve_batch(cfg, batch=args.batch, prompt_len=args.prompt_len,
                    gen=args.gen)
    print(f"[serve] prefill {r['prefill_s']:.3f}s "
          f"({r['prefill_tok_s']:.0f} tok/s), decode {r['decode_s']:.3f}s "
          f"({r['decode_tok_s']:.1f} tok/s), sample: "
          f"{r['tokens'][0, :8].tolist()}")


if __name__ == "__main__":
    main()
