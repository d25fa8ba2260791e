"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell and
extract the roofline terms from the compiled artifact.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \\
      --shape train_4k --mesh single --out results/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --all  # subprocess per cell

The environment lines below MUST stay above every jax import: jax locks
the platform and device count at first init, and the dry-run (only the
dry-run) needs 512 placeholder CPU devices to build the production meshes.
They pin the CPU even on a machine with a TPU, for this process and the
per-cell children it spawns (which inherit the environment): a TPU can
belong to one process only, and the dry-run never needs it.
"""

import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

import argparse     # noqa: E402
import json         # noqa: E402
import subprocess   # noqa: E402
import sys          # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402

import jax          # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import (ARCH_IDS, SHAPES_BY_NAME, get_config,  # noqa: E402
                           shape_applicable)
from repro.distributed import sharding as shd  # noqa: E402
from repro.distributed.hlo_analysis import (Roofline, collective_bytes,  # noqa: E402
                                            extract_cost)
from repro.distributed.hlo_cost import analyze as hlo_analyze  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.optim import OptConfig, init_opt_state, make_train_step  # noqa: E402


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------


def batch_struct(cfg, B, S):
    i32 = jnp.int32
    batch = {"inputs": jax.ShapeDtypeStruct((B, S), i32),
             "targets": jax.ShapeDtypeStruct((B, S), i32)}
    if cfg.frontend == "vision_patches":
        batch["patches"] = jax.ShapeDtypeStruct(
            (B, cfg.frontend_seq, cfg.d_model), jnp.bfloat16)
    elif cfg.frontend == "audio_frames":
        batch["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.frontend_seq, cfg.d_model), jnp.bfloat16)
    return batch


def batch_shardings(batch, mesh, cfg):
    out = {}
    for k, v in batch.items():
        if v.ndim == 2:
            out[k] = shd.tokens_sharding(mesh, v.shape)
        else:
            spec = shd.resolve_logical(("batch", None, None), v.shape, mesh, cfg)
            out[k] = NamedSharding(mesh, spec)
    return out


def input_specs(cfg, shape_name, mesh, opt=None):
    """Returns (step_fn, arg_structs tuple, in_shardings tuple, meta)."""
    sc = SHAPES_BY_NAME[shape_name]
    B, S = sc.global_batch, sc.seq_len
    params_s = jax.eval_shape(
        lambda: lm.init_lm(jax.random.PRNGKey(0), cfg))
    # decode uses the serve-time 2D weight sharding (no optimizer state to
    # co-shard; per-step FSDP gathers would dominate — see sharding.py)
    mode = "serve" if sc.kind == "decode" else "train"
    param_sh = shd.sharding_tree(params_s, mesh, cfg, mode)
    rep = NamedSharding(mesh, P())

    if sc.kind == "train":
        # bf16-param archs (400B class) also store bf16 optimizer moments
        opt = opt or OptConfig(microbatches=cfg.train_microbatches,
                               moment_dtype=("bfloat16"
                                             if cfg.param_dtype == "bfloat16"
                                             else "float32"))
        opt_s = jax.eval_shape(lambda: init_opt_state(params_s, opt))
        opt_sh = {"m": shd.sharding_tree(opt_s["m"], mesh, cfg),
                  "v": shd.sharding_tree(opt_s["v"], mesh, cfg),
                  "count": rep}
        batch = batch_struct(cfg, B, S)
        fn = make_train_step(cfg, opt)
        args = (params_s, opt_s, batch)
        shards = (param_sh, opt_sh, batch_shardings(batch, mesh, cfg))
        meta = {"tokens": B * S, "kind": "train"}
        return fn, args, shards, meta

    if sc.kind == "prefill":
        batch = {k: v for k, v in batch_struct(cfg, B, S).items()
                 if k != "targets"}

        def fn(params, batch):
            logits, caches, t = lm.prefill(params, batch, cfg, cache_len=S)
            return logits, caches

        args = (params_s, batch)
        shards = (param_sh, batch_shardings(batch, mesh, cfg))
        meta = {"tokens": B * S, "kind": "prefill"}
        return fn, args, shards, meta

    # decode: one new token against a cache/state of length S
    cache_len = S
    caches_s = jax.eval_shape(lambda: lm.init_caches(cfg, B, cache_len))
    caches_sh = [shd.cache_sharding_tree(seg, mesh, cfg) for seg in caches_s]
    token = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    t = jax.ShapeDtypeStruct((), jnp.int32)

    def fn(params, caches, token, t):
        return lm.decode_step(params, caches, token, t, cfg)

    args = (params_s, caches_s, token, t)
    shards = (param_sh, caches_sh, shd.tokens_sharding(mesh, (B, 1)), rep)
    meta = {"tokens": B, "kind": "decode"}
    return fn, args, shards, meta


# ---------------------------------------------------------------------------
# lower + compile + analyse one cell
# ---------------------------------------------------------------------------


def run_cell(arch, shape_name, mesh_kind, out_dir=None, save_hlo=False,
             attn_impl=None, overrides=None):
    cfg = get_config(arch)
    if attn_impl:
        cfg = cfg.replace(attn_impl=attn_impl)
    elif cfg.attn_impl == "xla":
        # production default: flash-class chunked attention. The naive
        # masked-softmax path (--attn-impl xla) is kept as the §Perf
        # baseline; at 32k context it needs O(S²) score buffers.
        cfg = cfg.replace(attn_impl="xla_chunked")
    if overrides:
        cfg = cfg.replace(**overrides)
    sc = SHAPES_BY_NAME[shape_name]
    ok, why = shape_applicable(cfg, sc)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "applicable": ok, "skip_reason": why,
           "params": cfg.param_count(),
           "active_params": cfg.active_param_count()}
    if not ok:
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = int(np.prod(mesh.devices.shape))
    t0 = time.time()
    mode = "serve" if SHAPES_BY_NAME[shape_name].kind == "decode" else "train"
    with mesh, shd.activation_sharding(mesh, cfg, mode):
        fn, args, shards, meta = input_specs(cfg, shape_name, mesh)
        # donate the mutated state (params+opt for train, caches for decode)
        donate = {"train": (0, 1), "decode": (1,), "prefill": ()}[meta["kind"]]
        jfn = jax.jit(fn, in_shardings=shards, donate_argnums=donate)
        lowered = jfn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    cost = extract_cost(compiled)       # XLA's own (loop bodies counted once)
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)        # ditto (kept for reference)
    # trip-count-aware walk of the compiled module (EXPERIMENTS.md §Roofline)
    tc, attn_tc = hlo_analyze(hlo, tag_re=r"flashattn|sdpattn")
    _, mix_tc = hlo_analyze(hlo, tag_re=r"wkvscan|rgscan|moeffn")
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument_size_bytes": getattr(ma, "argument_size_in_bytes", None),
            "output_size_bytes": getattr(ma, "output_size_in_bytes", None),
            "temp_size_bytes": getattr(ma, "temp_size_in_bytes", None),
            "generated_code_size_bytes":
                getattr(ma, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # noqa: BLE001
        mem = {"error": str(e)}
    # useful model flops: 6·N·D train, 2·N_active·D serve
    n_active = cfg.active_param_count()
    mult = 6 if meta["kind"] == "train" else 2
    model_flops = mult * n_active * meta["tokens"]
    roof = Roofline(
        flops_per_device=tc.flops,
        hbm_bytes_per_device=tc.bytes,
        collective_bytes_per_device=tc.coll_total,
        chips=chips, model_flops=model_flops,
        collectives={k: round(v) for k, v in tc.coll.items() if v})
    rec.update({
        "chips": chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory_analysis": mem,
        "cost_analysis": cost,
        "roofline": roof.to_dict(),
        "attn_tagged": {"flops": attn_tc.flops, "bytes": attn_tc.bytes},
        "mixer_tagged": {"flops": mix_tc.flops, "bytes": mix_tc.bytes},
        "hlo_bytes": len(hlo),
    })
    if save_hlo and out_dir:
        with open(os.path.join(
                out_dir, f"{arch}_{shape_name}_{mesh_kind}.hlo.txt"), "w") as f:
            f.write(hlo)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--attn-impl", default=None,
                    help="force attention impl (xla = naive baseline)")
    ap.add_argument("--override", default=None,
                    help="JSON dict of ModelConfig field overrides")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    if args.all:
        cells = [(a, s, m) for a in ARCH_IDS
                 for s in ("train_4k", "prefill_32k", "decode_32k", "long_500k")
                 for m in ("single", "multi")]
        for arch, shape, meshk in cells:
            out_file = os.path.join(args.out, f"{arch}_{shape}_{meshk}.json")
            if os.path.exists(out_file):
                print(f"[skip] {arch} {shape} {meshk} (exists)", flush=True)
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", meshk,
                   "--out", args.out]
            print(f"[cell] {arch} {shape} {meshk} ...", flush=True)
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=3600)
            if r.returncode != 0:
                err = {"arch": arch, "shape": shape, "mesh": meshk,
                       "applicable": True, "error": r.stderr[-4000:]}
                with open(out_file, "w") as f:
                    json.dump(err, f, indent=1)
                print(f"  FAILED (see {out_file})", flush=True)
            else:
                print("  ok", flush=True)
        return

    rec = run_cell(args.arch, args.shape, args.mesh, args.out, args.save_hlo,
                   attn_impl=args.attn_impl,
                   overrides=json.loads(args.override) if args.override
                   else None)
    suffix = f"_{args.attn_impl}" if args.attn_impl else ""
    if args.override:
        suffix += "_ovr" + str(abs(hash(args.override)) % 10000)
    out_file = os.path.join(
        args.out, f"{args.arch}_{args.shape}_{args.mesh}{suffix}.json")
    with open(out_file, "w") as f:
        json.dump(rec, f, indent=1)
    if rec.get("applicable") and "roofline" in rec:
        r = rec["roofline"]
        print(f"{args.arch} {args.shape} {args.mesh}: chips={rec['chips']} "
              f"compile={rec['compile_s']}s "
              f"t_comp={r['t_compute_s']:.4f}s t_mem={r['t_memory_s']:.4f}s "
              f"t_coll={r['t_collective_s']:.4f}s bottleneck={r['bottleneck']} "
              f"mfr={r['model_flops_ratio']:.3f} "
              f"roofline_frac={r['roofline_fraction']:.3f}")
        print("memory_analysis:", rec["memory_analysis"])
        print("cost_analysis:", rec["cost_analysis"])
    else:
        print(f"{args.arch} {args.shape} {args.mesh}: "
              f"SKIP — {rec.get('skip_reason')}")


if __name__ == "__main__":
    main()
