"""GQA/MQA attention: full, local-window, cross; train / prefill / decode.

Layouts: q proj (d, H, hd); k/v proj (d, KV, hd); o proj (H, hd, d).
Head axes are sharded over the ``model`` mesh axis when divisible (see
distributed/sharding.py); otherwise attention params are replicated on
``model`` and the MLP carries the tensor parallelism.

``attn_impl`` selects the sequence-mixing implementation for the quadratic
region: "xla" (masked softmax, used by dry-runs/rooflines), "pallas" (TPU
flash kernel) or "pallas_interpret" (kernel body interpreted on CPU, used by
tests). Decode is always XLA (one query token).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import constrain, use_context_parallel
from repro.models.common import apply_rope, dense_init, rms_norm, split_keys

NEG_INF = -0.7 * float(np.finfo(np.float32).max)


def _cp(x, seq_dim, n_heads):
    """Context-parallel constraint: shard the query-sequence axis over the
    ``model`` mesh axis when heads cannot shard (see sharding.py)."""
    if not use_context_parallel(n_heads):
        return x
    logical = [None] * x.ndim
    logical[0] = "batch"
    logical[seq_dim] = "model"
    return constrain(x, tuple(logical))


def init_attention(key, cfg, cross: bool = False):
    dt = jnp.dtype(cfg.param_dtype)
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = split_keys(key, ["wq", "wk", "wv", "wo", "qs", "ks"])
    p = {
        "wq": dense_init(ks["wq"], (d, H, hd), d, dt),
        "wk": dense_init(ks["wk"], (d, KV, hd), d, dt),
        "wv": dense_init(ks["wv"], (d, KV, hd), d, dt),
        "wo": dense_init(ks["wo"], (H, hd, d), H * hd, dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
    return p


def _qkv(p, x, kv_x, positions, cfg, rope: bool = True):
    cdt = jnp.dtype(cfg.compute_dtype)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cdt))
    k = jnp.einsum("bsd,dhk->bshk", kv_x, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", kv_x, p["wv"].astype(cdt))
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope and positions is not None:
        q = apply_rope(q, positions, cfg)
        k = apply_rope(k, positions, cfg)
    return q, k, v


def _blocks(x, nb, block):
    """(B,T,KV,hd) -> (nb, B, block, KV, hd)"""
    B, T, KV, hd = x.shape
    return x.reshape(B, nb, block, KV, hd).transpose(1, 0, 2, 3, 4)


def _block_mask(q_pos, pc, causal, window):
    mask = make_mask(q_pos, pc, causal, window)
    return mask[..., None, None, :, :] if mask.ndim == 2 else \
        mask[:, None, None, :, :]


def _flash_fwd_scan(qf, k, v, q_pos, k_pos, causal, window, nb, block):
    kb, vb = _blocks(k, nb, block), _blocks(v, nb, block)
    pb = (k_pos.reshape(nb, block) if k_pos.ndim == 1 else
          k_pos.reshape(k_pos.shape[0], nb, block).transpose(1, 0, 2))

    def body(carry, xs):
        m, l, acc = carry
        kc, vc, pc = xs
        s = jnp.einsum("bqkgh,bskh->bkgqs", qf, kc.astype(jnp.float32))
        mask = _block_mask(q_pos, pc, causal, window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None]) * mask
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskh->bkgqh", p, vc.astype(jnp.float32))
        return (m_new, l, acc), None

    B, S = qf.shape[0], qf.shape[1]
    KV, G, hd = qf.shape[2], qf.shape[3], qf.shape[4]
    qf = _cp(qf, 1, KV * G)
    m0 = _cp(jnp.full((B, KV, G, S), NEG_INF, jnp.float32), 3, KV * G)
    l0 = _cp(jnp.zeros((B, KV, G, S), jnp.float32), 3, KV * G)
    a0 = _cp(jnp.zeros((B, KV, G, S, hd), jnp.float32), 3, KV * G)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, pb))
    l = jnp.maximum(l, 1e-20)
    out = acc / l[..., None]
    lse = m + jnp.log(l)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_xla(q, k, v, q_pos, k_pos, causal, window, block):
    """Flash attention in pure XLA: lax.scan over key blocks, online softmax
    forward, recomputation-based backward (custom_vjp) — neither pass ever
    materializes the (Sq,Sk) score tensor or stacks per-block residuals.
    This is the same schedule as the Pallas kernel, expressed at the XLA
    level so it lowers on any backend (and is what dry-runs measure).

    q (B,S,H,hd); k/v (B,T,KV,hd). Returns (B,S,H,hd)."""
    out, _ = _flash_xla_fwd(q, k, v, q_pos, k_pos, causal, window, block)
    return out


def _prep(q, k, block):
    B, S, H, hd = q.shape
    T = k.shape[1]
    KV = k.shape[2]
    nb = T // block if block and T % block == 0 else 1
    block = T // nb
    qf = q.reshape(B, S, KV, H // KV, hd).astype(jnp.float32) / np.sqrt(hd)
    return qf, nb, block


def _flash_xla_fwd(q, k, v, q_pos, k_pos, causal, window, block):
    qf, nb, block = _prep(q, k, block)
    with jax.named_scope("flashattn"):
        out, lse = _flash_fwd_scan(qf, k, v, q_pos, k_pos, causal, window,
                                   nb, block)
    B, S, H, hd = q.shape
    o = out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd).astype(q.dtype)
    return o, (q, k, v, q_pos, k_pos, o, lse)


def _flash_xla_bwd(causal, window, block, res, g):
    with jax.named_scope("flashattn"):
        return _flash_xla_bwd_inner(causal, window, block, res, g)


def _flash_xla_bwd_inner(causal, window, block, res, g):
    q, k, v, q_pos, k_pos, o, lse = res
    qf, nb, block = _prep(q, k, block)
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qf = _cp(qf, 1, KV * G)
    gf = g.reshape(B, S, KV, G, hd).astype(jnp.float32)
    gf = _cp(gf.transpose(0, 2, 3, 1, 4), 3, KV * G)       # (B,KV,G,S,hd)
    of = o.reshape(B, S, KV, G, hd).astype(jnp.float32).transpose(0, 2, 3, 1, 4)
    of = _cp(of, 3, KV * G)
    delta = (gf * of).sum(-1)                              # (B,KV,G,S)
    kb, vb = _blocks(k, nb, block), _blocks(v, nb, block)
    pb = (k_pos.reshape(nb, block) if k_pos.ndim == 1 else
          k_pos.reshape(k_pos.shape[0], nb, block).transpose(1, 0, 2))

    def body(dq, xs):
        kc, vc, pc = xs
        s = jnp.einsum("bqkgh,bskh->bkgqs", qf, kc.astype(jnp.float32))
        mask = _block_mask(q_pos, pc, causal, window)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[..., None]) * mask             # (B,KV,G,S,blk)
        dv = jnp.einsum("bkgqs,bkgqh->bskh", p, gf)
        dp = jnp.einsum("bkgqh,bskh->bkgqs", gf, vc.astype(jnp.float32))
        ds = p * (dp - delta[..., None])                   # scale folded in qf
        dk = jnp.einsum("bkgqs,bqkgh->bskh", ds, qf)
        dq = dq + jnp.einsum("bkgqs,bskh->bkgqh", ds, kc.astype(jnp.float32))
        return dq, (dk, dv)

    dq0 = _cp(jnp.zeros((B, KV, G, S, hd), jnp.float32), 3, KV * G)
    dq, (dkb, dvb) = jax.lax.scan(body, dq0, (kb, vb, pb))
    dq = (dq.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)
          / np.sqrt(hd)).astype(q.dtype)
    dk = dkb.transpose(1, 0, 2, 3, 4).reshape(B, nb * block, KV, hd).astype(k.dtype)
    dv = dvb.transpose(1, 0, 2, 3, 4).reshape(B, nb * block, KV, hd).astype(v.dtype)
    return dq, dk, dv, None, None


_flash_xla.defvjp(_flash_xla_fwd, _flash_xla_bwd)


def _sdpa_xla_chunked(q, k, v, q_pos, k_pos, cfg, *, causal, window,
                      block=512):
    assert cfg.attn_logit_softcap == 0.0, \
        "xla_chunked path does not support logit softcap"
    T = k.shape[1]
    pad = (-T) % block
    if pad and T > block:
        # pad keys to a block multiple; padded slots get position -1 so the
        # mask removes them (exactly like the Pallas kernel's tail masking)
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        widths = ((0, pad),) if k_pos.ndim == 1 else ((0, 0), (0, pad))
        k_pos = jnp.pad(k_pos, widths, constant_values=-1)
    return _flash_xla(q, k, v, q_pos, k_pos, causal, window, block)


def _sdpa_xla(q, k, v, mask, cfg):
    """q (B,S,H,hd), k/v (B,T,KV,hd), mask broadcastable to (B,KV,G,S,T)."""
    with jax.named_scope("sdpattn"):
        return _sdpa_xla_inner(q, k, v, mask, cfg)


def _sdpa_xla_inner(q, k, v, mask, cfg):
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    q = _cp(q.reshape(B, S, KV, G, hd), 1, H)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32)
    scores = scores / np.sqrt(hd)
    if cfg.attn_logit_softcap > 0:
        c = cfg.attn_logit_softcap
        scores = c * jnp.tanh(scores / c)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v)
    return out.reshape(B, S, H, hd)


def make_mask(q_pos, k_pos, causal: bool, window: int):
    """Boolean mask (…, S, T): True = attend. Positions may be (S,)/(T,) or
    batched (B, S)/(B, T); invalid cache slots carry position -1."""
    q = q_pos[..., :, None]
    kk = k_pos[..., None, :]
    m = kk >= 0
    if causal:
        m &= kk <= q
    if window > 0:
        m &= kk > q - window
    return m


def _proj_out(p, out, cfg):
    cdt = jnp.dtype(cfg.compute_dtype)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cdt))


def attn_fwd(p, x, positions, cfg, *, causal=True, window=0, kv_x=None,
             rope=True):
    """Full-sequence attention (training / encoder). Returns (B,S,d)."""
    kv_x = x if kv_x is None else kv_x
    q, k, v = _qkv(p, x, kv_x, positions, cfg, rope=rope)
    if cfg.attn_impl in ("pallas", "pallas_interpret") and causal and kv_x is x:
        from repro.kernels import ops as kops
        # "pallas" auto-resolves: compiled on TPU, interpret elsewhere
        out = kops.flash_attention(
            q, k, v, causal=True, window=window,
            softcap=cfg.attn_logit_softcap,
            interpret=(True if cfg.attn_impl == "pallas_interpret" else None))
    elif cfg.attn_impl == "xla_chunked" and kv_x is x:
        pos = positions if positions is not None else jnp.arange(x.shape[1])
        out = _sdpa_xla_chunked(q, k, v, pos, pos, cfg, causal=causal,
                                window=window)
    else:
        mask = None
        if causal or window > 0:
            pos = positions if positions is not None else jnp.arange(x.shape[1])
            mask = make_mask(pos, pos, causal, window)
            # (S,T) or (B,S,T) -> broadcast over (KV,G)
            mask = mask[..., None, None, :, :] if mask.ndim == 2 else \
                mask[:, None, None, :, :]
        out = _sdpa_xla(q, k, v, mask, cfg)
    return _proj_out(p, out, cfg)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------


def init_cache(cfg, batch, length, window: int = 0, dtype=None):
    """Cache for one attention layer. Ring buffer when window>0."""
    dt = dtype or jnp.dtype(cfg.compute_dtype)
    L = min(window, length) if window > 0 else length
    return {
        "k": jnp.zeros((batch, L, cfg.n_kv_heads, cfg.head_dim), dt),
        "v": jnp.zeros((batch, L, cfg.n_kv_heads, cfg.head_dim), dt),
        "pos": jnp.full((L,), -1, jnp.int32),
    }


def attn_prefill(p, x, positions, cfg, *, cache, window=0):
    """Causal attention over the prompt; fills cache slots [0, S)."""
    kv_x = x
    q, k, v = _qkv(p, x, kv_x, positions, cfg)
    S = x.shape[1]
    L = cache["k"].shape[1]
    if L >= S:
        cache = {
            "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), 0, 1),
            "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), 0, 1),
            "pos": jax.lax.dynamic_update_slice_in_dim(
                cache["pos"], positions.astype(jnp.int32), 0, 0),
        }
    else:  # ring buffer smaller than the prompt: keep the last L entries
        cache = {
            "k": k[:, S - L:].astype(cache["k"].dtype),
            "v": v[:, S - L:].astype(cache["v"].dtype),
            "pos": positions[S - L:].astype(jnp.int32),
        }
    if cfg.attn_impl in ("xla_chunked", "pallas", "pallas_interpret"):
        out = _sdpa_xla_chunked(q, k, v, positions, positions, cfg,
                                causal=True, window=window)
    else:
        mask = make_mask(positions, positions, True, window)
        mask = mask[None, None, None] if mask.ndim == 2 else mask[:, None, None]
        out = _sdpa_xla(q, k, v, mask, cfg)
    return _proj_out(p, out, cfg), cache


def attn_decode(p, x, t, cfg, *, cache, window=0, cross=False):
    """One-token decode. x (B,1,d); t scalar int32 = current position.

    Full cache: write at slot t. Ring cache (window>0): write at t mod W.
    Cross attention: cache is read-only (encoder K/V), no rope.
    """
    if cross:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k, v = cache["k"], cache["v"]
        mask = (cache["pos"] >= 0)[None, None, None, None, :]
        out = _sdpa_xla(q, k, v, mask, cfg)
        return _proj_out(p, out, cfg), cache
    pos = jnp.full((x.shape[0], 1), t, jnp.int32)
    q, k, v = _qkv(p, x, x, pos, cfg)
    L = cache["k"].shape[1]
    slot = t % L if window > 0 else t
    cache = {
        "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0)),
        "pos": jax.lax.dynamic_update_slice(cache["pos"],
                                            jnp.full((1,), t, jnp.int32), (slot,)),
    }
    mask = make_mask(pos[0], cache["pos"], True, window)[None, None, None]
    out = _sdpa_xla(q, cache["k"], cache["v"], mask, cfg)
    return _proj_out(p, out, cfg), cache


def init_paged_cache(cfg, n_pages, page_size, dtype=None):
    """Paged cache for one attention layer: a shared pool of fixed-size
    K/V pages. ``n_pages`` includes any reserved trash page the caller
    points inactive rows at; rows map logical->physical pages via the
    block tables threaded through the paged attn calls."""
    dt = dtype or jnp.dtype(cfg.compute_dtype)
    shape = (n_pages, cfg.n_kv_heads, page_size, cfg.head_dim)
    return {"k_pages": jnp.zeros(shape, dt), "v_pages": jnp.zeros(shape, dt)}


def _paged_write(cache, k, v, page_ids, slots):
    """Scatter new K/V into pages. k/v (B,S,KV,hd); page_ids/slots (B,S).
    Duplicate (page, slot) targets only occur on the trash page (inactive
    rows), where last-write-wins is harmless."""
    B, S, KV, hd = k.shape
    pid = page_ids.reshape(-1)
    sl = slots.reshape(-1)
    kf = k.astype(cache["k_pages"].dtype).reshape(B * S, KV, hd)
    vf = v.astype(cache["v_pages"].dtype).reshape(B * S, KV, hd)
    return {"k_pages": cache["k_pages"].at[pid, :, sl].set(kf),
            "v_pages": cache["v_pages"].at[pid, :, sl].set(vf)}


def paged_attn_prefill(p, x, positions, cfg, *, cache, block_tables):
    """Prompt attention for freshly admitted rows, writing K/V into the
    rows' pages. x (B,S,d); positions (S,) = arange(S) for fresh rows;
    block_tables (B,maxp). Causal over the prompt itself (the pages hold
    nothing older). Returns (out (B,S,d), cache)."""
    q, k, v = _qkv(p, x, x, positions, cfg)
    page_size = cache["k_pages"].shape[2]
    page_ids = block_tables[:, positions // page_size]          # (B,S)
    slots = jnp.broadcast_to((positions % page_size)[None],
                             page_ids.shape)
    cache = _paged_write(cache, k, v, page_ids, slots)
    mask = make_mask(positions, positions, True, 0)[None, None, None]
    out = _sdpa_xla(q, k, v, mask, cfg)
    return _proj_out(p, out, cfg), cache


def paged_attn_decode(p, x, positions, cfg, *, cache, block_tables,
                      lengths, interpret=None):
    """One-token decode over the paged cache. x (B,1,d); positions (B,)
    per-row write position of the new token; lengths (B,) valid K/V count
    *including* the new token (0 = inactive slot — its block table points
    at the trash page, its output row is zero). Returns (out, cache)."""
    pos = positions[:, None]                                    # (B,1)
    q, k, v = _qkv(p, x, x, pos, cfg)
    page_size = cache["k_pages"].shape[2]
    page_ids = jnp.take_along_axis(block_tables,
                                   (positions // page_size)[:, None], axis=1)
    cache = _paged_write(cache, k, v, page_ids,
                         (positions % page_size)[:, None])
    from repro.kernels import ops as kops
    out = kops.paged_decode_attention(
        q, cache["k_pages"], cache["v_pages"], block_tables, lengths,
        page_size=page_size, interpret=interpret)
    return _proj_out(p, out, cfg), cache


def init_cross_cache(p, enc_out, cfg):
    """Precompute encoder K/V for cross-attention (whisper decoder)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(cdt))
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    pos = jnp.arange(enc_out.shape[1], dtype=jnp.int32)
    return {"k": k, "v": v, "pos": pos}
