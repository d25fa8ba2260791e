"""Layer-kind dispatch and stacked-segment machinery.

A model is a sequence of *segments*; a segment is ``(kinds, repeats)`` where
``kinds`` is the tuple of layer kinds forming one repeating block. Per-layer
parameters are stacked along a leading ``repeats`` axis and the segment is
executed with ``lax.scan`` (keeps HLO size depth-independent; optional remat
policy wraps the scanned body). Caches/states for decode are likewise stacked.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models import attention as attn
from repro.models import ssm
from repro.models.common import init_norm, norm_fwd, split_keys
from repro.models.mlp import init_mlp, mlp_fwd
from repro.models.moe import init_moe, moe_fwd

ATTN_KINDS = ("attn", "attn_local", "enc_attn", "dec_attn", "moe",
              "attn_local_moe")

# layer kinds the paged decode path supports: dense causal full attention
# (windowed/ring, cross-attn and SSM caches have no paged layout)
PAGED_KINDS = ("attn",)


def _window(kind, cfg):
    return cfg.attn_window if kind in ("attn_local", "attn_local_moe") else 0


# ---------------------------------------------------------------------------
# single-layer init / apply
# ---------------------------------------------------------------------------


def init_layer(key, kind, cfg):
    ks = split_keys(key, ["a", "b", "c", "d"])
    p: Dict[str, Any] = {"norm1": init_norm(cfg), "norm2": init_norm(cfg)}
    if kind in ("attn", "attn_local", "enc_attn"):
        p["attn"] = attn.init_attention(ks["a"], cfg)
        p["mlp"] = init_mlp(ks["b"], cfg)
    elif kind == "dec_attn":
        p["attn"] = attn.init_attention(ks["a"], cfg)
        p["norm_x"] = init_norm(cfg)
        p["xattn"] = attn.init_attention(ks["c"], cfg, cross=True)
        p["mlp"] = init_mlp(ks["b"], cfg)
    elif kind in ("moe", "attn_local_moe"):
        p["attn"] = attn.init_attention(ks["a"], cfg)
        p["moe"] = init_moe(ks["b"], cfg)
    elif kind == "rglru":
        p["rec"] = ssm.init_rglru(ks["a"], cfg)
        p["mlp"] = init_mlp(ks["b"], cfg)
    elif kind == "rwkv":
        p["tm"] = ssm.init_rwkv(ks["a"], cfg)
    else:
        raise ValueError(kind)
    return p


def init_layer_cache(kind, cfg, batch, length):
    if kind in ("attn", "moe"):
        return attn.init_cache(cfg, batch, length)
    if kind in ("attn_local", "attn_local_moe"):
        return attn.init_cache(cfg, batch, length, window=cfg.attn_window)
    if kind == "dec_attn":
        return {"self": attn.init_cache(cfg, batch, length),
                "cross": attn.init_cache(cfg, batch, cfg.frontend_seq)}
    if kind == "rglru":
        return ssm.init_rglru_state(cfg, batch)
    if kind == "rwkv":
        return ssm.init_rwkv_state(cfg, batch)
    raise ValueError(kind)


def layer_fwd(kind, p, x, ctx, cfg):
    """Full-sequence forward (training / encoder). Returns (x, aux)."""
    aux = {}
    if kind in ATTN_KINDS:
        h = norm_fwd(p["norm1"], x, cfg)
        causal = kind != "enc_attn"
        h = attn.attn_fwd(p["attn"], h, ctx["positions"], cfg, causal=causal,
                          window=_window(kind, cfg))
        x = constrain(x + h, ("batch", "seq", None))
        if kind == "dec_attn":
            h = norm_fwd(p["norm_x"], x, cfg)
            h = attn.attn_fwd(p["xattn"], h, None, cfg, causal=False,
                              kv_x=ctx["enc_out"], rope=False)
            x = x + h
        h = norm_fwd(p["norm2"], x, cfg)
        if kind in ("moe", "attn_local_moe"):
            h, aux = moe_fwd(p["moe"], h, cfg)
        else:
            h = mlp_fwd(p["mlp"], h, cfg)
        x = constrain(x + h, ("batch", "seq", None))
    elif kind == "rglru":
        h = norm_fwd(p["norm1"], x, cfg)
        st = ssm.init_rglru_state(cfg, x.shape[0])
        h, _ = ssm.rglru_block(p["rec"], h, st, cfg)
        x = x + h
        h = norm_fwd(p["norm2"], x, cfg)
        x = constrain(x + mlp_fwd(p["mlp"], h, cfg), ("batch", "seq", None))
    elif kind == "rwkv":
        h = norm_fwd(p["norm1"], x, cfg)
        st = ssm.init_rwkv_state(cfg, x.shape[0])
        h, _ = ssm.rwkv_timemix(p["tm"], h, st, cfg)
        x = x + h
        h = norm_fwd(p["norm2"], x, cfg)
        h, _ = ssm.rwkv_channelmix(p["tm"], h, st, cfg)
        x = constrain(x + h, ("batch", "seq", None))
    else:
        raise ValueError(kind)
    return x, aux


def layer_prefill(kind, p, x, ctx, cfg, cache):
    """Prompt forward filling the cache. Returns (x, cache)."""
    if kind in ATTN_KINDS:
        h = norm_fwd(p["norm1"], x, cfg)
        if kind == "dec_attn":
            h, self_c = attn.attn_prefill(p["attn"], h, ctx["positions"], cfg,
                                          cache=cache["self"])
            x = x + h
            hx = norm_fwd(p["norm_x"], x, cfg)
            cross_c = attn.init_cross_cache(p["xattn"], ctx["enc_out"], cfg)
            hx = attn.attn_fwd(p["xattn"], hx, None, cfg, causal=False,
                               kv_x=ctx["enc_out"], rope=False)
            x = x + hx
            cache = {"self": self_c, "cross": cross_c}
        else:
            h, cache = attn.attn_prefill(p["attn"], h, ctx["positions"], cfg,
                                         cache=cache, window=_window(kind, cfg))
            x = x + h
        h = norm_fwd(p["norm2"], x, cfg)
        h = moe_fwd(p["moe"], h, cfg)[0] if kind in ("moe", "attn_local_moe") \
            else mlp_fwd(p["mlp"], h, cfg)
        x = x + h
    elif kind == "rglru":
        h = norm_fwd(p["norm1"], x, cfg)
        h, cache = ssm.rglru_block(p["rec"], h, cache, cfg)
        x = x + h
        x = x + mlp_fwd(p["mlp"], norm_fwd(p["norm2"], x, cfg), cfg)
    elif kind == "rwkv":
        h = norm_fwd(p["norm1"], x, cfg)
        h, cache = ssm.rwkv_timemix(p["tm"], h, cache, cfg)
        x = x + h
        h = norm_fwd(p["norm2"], x, cfg)
        h, cache = ssm.rwkv_channelmix(p["tm"], h, cache, cfg)
        x = x + h
    else:
        raise ValueError(kind)
    return x, cache


def layer_decode(kind, p, x, t, cfg, cache, ctx=None):
    """Single-token step. x (B,1,d). Returns (x, cache)."""
    if kind in ATTN_KINDS:
        h = norm_fwd(p["norm1"], x, cfg)
        if kind == "dec_attn":
            h, self_c = attn.attn_decode(p["attn"], h, t, cfg, cache=cache["self"])
            x = x + h
            hx = norm_fwd(p["norm_x"], x, cfg)
            hx, _ = attn.attn_decode(p["xattn"], hx, t, cfg,
                                     cache=cache["cross"], cross=True)
            x = x + hx
            cache = {"self": self_c, "cross": cache["cross"]}
        else:
            h, cache = attn.attn_decode(p["attn"], h, t, cfg, cache=cache,
                                        window=_window(kind, cfg))
            x = x + h
        h = norm_fwd(p["norm2"], x, cfg)
        h = moe_fwd(p["moe"], h, cfg)[0] if kind in ("moe", "attn_local_moe") \
            else mlp_fwd(p["mlp"], h, cfg)
        x = x + h
    elif kind == "rglru":
        h = norm_fwd(p["norm1"], x, cfg)
        h, cache = ssm.rglru_block(p["rec"], h, cache, cfg)
        x = x + h
        x = x + mlp_fwd(p["mlp"], norm_fwd(p["norm2"], x, cfg), cfg)
    elif kind == "rwkv":
        h = norm_fwd(p["norm1"], x, cfg)
        h, cache = ssm.rwkv_timemix(p["tm"], h, cache, cfg, chunk=1)
        x = x + h
        h = norm_fwd(p["norm2"], x, cfg)
        h, cache = ssm.rwkv_channelmix(p["tm"], h, cache, cfg)
        x = x + h
    else:
        raise ValueError(kind)
    return x, cache


def init_layer_paged_cache(kind, cfg, n_pages, page_size, dtype=None):
    if kind not in PAGED_KINDS:
        raise ValueError(
            f"paged decode supports dense causal {PAGED_KINDS} layers, "
            f"got {kind!r}")
    return attn.init_paged_cache(cfg, n_pages, page_size, dtype=dtype)


def layer_paged_prefill(kind, p, x, ctx, cfg, cache):
    """Prompt forward for fresh rows, writing K/V into their pages."""
    assert kind in PAGED_KINDS, kind
    h = norm_fwd(p["norm1"], x, cfg)
    h, cache = attn.paged_attn_prefill(p["attn"], h, ctx["positions"], cfg,
                                       cache=cache,
                                       block_tables=ctx["block_tables"])
    x = x + h
    x = x + mlp_fwd(p["mlp"], norm_fwd(p["norm2"], x, cfg), cfg)
    return x, cache


def layer_paged_decode(kind, p, x, ctx, cfg, cache):
    """Single-token step over the paged cache. x (B,1,d)."""
    assert kind in PAGED_KINDS, kind
    h = norm_fwd(p["norm1"], x, cfg)
    h, cache = attn.paged_attn_decode(p["attn"], h, ctx["positions"], cfg,
                                      cache=cache,
                                      block_tables=ctx["block_tables"],
                                      lengths=ctx["lengths"],
                                      interpret=ctx.get("interpret"))
    x = x + h
    x = x + mlp_fwd(p["mlp"], norm_fwd(p["norm2"], x, cfg), cfg)
    return x, cache


# ---------------------------------------------------------------------------
# segments (stacked layers, lax.scan)
# ---------------------------------------------------------------------------


def init_segment(key, kinds, repeats, cfg):
    """Stacked params: leaves have leading (repeats,) axis."""
    def one(k):
        ks = jax.random.split(k, len(kinds))
        return {f"{i}_{kind}": init_layer(ks[i], kind, cfg)
                for i, kind in enumerate(kinds)}
    keys = jax.random.split(key, repeats)
    stacked = [one(k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *stacked)


def init_segment_cache(kinds, repeats, cfg, batch, length):
    one = {f"{i}_{kind}": init_layer_cache(kind, cfg, batch, length)
           for i, kind in enumerate(kinds)}
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (repeats,) + x.shape),
                        one)


def _remat(fn, cfg):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_saveable)


def segment_fwd(seg_params, x, kinds, ctx, cfg):
    """Training/encoder forward through a stacked segment.
    Returns (x, aux_sums)."""
    def body(carry, layer_params):
        h = carry
        auxs = {}
        for i, kind in enumerate(kinds):
            h, aux = layer_fwd(kind, layer_params[f"{i}_{kind}"], h, ctx, cfg)
            for k, v in aux.items():
                auxs[k] = auxs.get(k, 0.0) + v
        pad = {k: jnp.zeros(()) for k in
               ("moe_lb_loss", "moe_z_loss", "moe_drop_frac")}
        pad.update(auxs)
        return h, pad

    if cfg.scan_layers:
        x, auxs = jax.lax.scan(_remat(body, cfg), x, seg_params)
        auxs = jax.tree.map(jnp.sum, auxs)
    else:
        reps = jax.tree.leaves(seg_params)[0].shape[0]
        auxs = None
        for r in range(reps):
            lp = jax.tree.map(lambda a: a[r], seg_params)
            x, a = body(x, lp)
            auxs = a if auxs is None else jax.tree.map(jnp.add, auxs, a)
    return x, auxs


def segment_prefill(seg_params, x, kinds, ctx, cfg, caches):
    """Prefill through a stacked segment; caches are stacked like params."""
    def body(carry, xs):
        layer_params, cache = xs
        h = carry
        new_caches = {}
        for i, kind in enumerate(kinds):
            key = f"{i}_{kind}"
            h, c = layer_prefill(kind, layer_params[key], h, ctx, cfg,
                                 cache[key])
            new_caches[key] = c
        return h, new_caches

    if cfg.scan_layers:
        x, caches = jax.lax.scan(body, x, (seg_params, caches))
    else:
        reps = jax.tree.leaves(seg_params)[0].shape[0]
        outs = []
        for r in range(reps):
            lp = jax.tree.map(lambda a: a[r], seg_params)
            cc = jax.tree.map(lambda a: a[r], caches)
            x, c = body(x, (lp, cc))
            outs.append(c)
        caches = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    return x, caches


def init_segment_paged_cache(kinds, repeats, cfg, n_pages, page_size,
                             dtype=None):
    one = {f"{i}_{kind}": init_layer_paged_cache(kind, cfg, n_pages,
                                                 page_size, dtype=dtype)
           for i, kind in enumerate(kinds)}
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (repeats,) + x.shape),
                        one)


def _segment_paged(layer_fn, seg_params, x, kinds, ctx, cfg, caches):
    def body(carry, xs):
        layer_params, cache = xs
        h = carry
        new_caches = {}
        for i, kind in enumerate(kinds):
            key = f"{i}_{kind}"
            h, c = layer_fn(kind, layer_params[key], h, ctx, cfg, cache[key])
            new_caches[key] = c
        return h, new_caches

    if cfg.scan_layers:
        x, caches = jax.lax.scan(body, x, (seg_params, caches))
    else:
        reps = jax.tree.leaves(seg_params)[0].shape[0]
        outs = []
        for r in range(reps):
            lp = jax.tree.map(lambda a: a[r], seg_params)
            cc = jax.tree.map(lambda a: a[r], caches)
            x, c = body(x, (lp, cc))
            outs.append(c)
        caches = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    return x, caches


def segment_paged_prefill(seg_params, x, kinds, ctx, cfg, caches):
    """Prompt prefill through a stacked segment into paged caches.
    ctx: positions (S,), block_tables (B,maxp)."""
    return _segment_paged(layer_paged_prefill, seg_params, x, kinds, ctx,
                          cfg, caches)


def segment_paged_decode(seg_params, x, kinds, ctx, cfg, caches):
    """Single-token step through a stacked segment over paged caches.
    ctx: positions (B,), block_tables (B,maxp), lengths (B,),
    interpret (static)."""
    return _segment_paged(layer_paged_decode, seg_params, x, kinds, ctx,
                          cfg, caches)


def segment_decode(seg_params, x, t, kinds, cfg, caches, ctx=None):
    def body(carry, xs):
        layer_params, cache = xs
        h = carry
        new_caches = {}
        for i, kind in enumerate(kinds):
            key = f"{i}_{kind}"
            h, c = layer_decode(kind, layer_params[key], h, t, cfg,
                                cache[key], ctx=ctx)
            new_caches[key] = c
        return h, new_caches

    if cfg.scan_layers:
        x, caches = jax.lax.scan(body, x, (seg_params, caches))
    else:
        reps = jax.tree.leaves(seg_params)[0].shape[0]
        outs = []
        for r in range(reps):
            lp = jax.tree.map(lambda a: a[r], seg_params)
            cc = jax.tree.map(lambda a: a[r], caches)
            x, c = body(x, (lp, cc))
            outs.append(c)
        caches = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    return x, caches
