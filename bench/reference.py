"""The benchmark's own weights and plain float32 reference of the payload
models. Imports nothing of the program.

Weights: ``generator_params`` and ``scorer_params`` build a model from a
key in float32 (the configuration's parameter type) and in the program's
parameter layout, so the same arrays can be handed to the program and to
the reference. ``archs/dense.py`` binds them and the forward below.

Reference: a straightforward ``jax.numpy`` forward in float32 at
``precision="highest"``, one layer after the other, following the model
definitions the configuration file names:

- generator (``progen-s``): structure prefix ``backbone[:prefix] @ W_s``,
  then BOS and the sampled tokens; pre-norm RMSNorm decoder layers with
  causal GQA attention (half-rotation RoPE) and a SwiGLU MLP; final
  RMSNorm and LM head; log-softmax over the real vocabulary (the padded
  rows of the head never sample).
- scorer (``foldscore-s`` / ``foldscore-m``): token embedding plus the
  projected target descriptor, the same causal decoder stack, final
  RMSNorm; pLDDT = mean of ``100 sigmoid(h w_plddt)``, pTM = ``sigmoid``
  of the mean of ``h w_ptm``, pAE = mean over the two inter-chain blocks
  of ``30 sigmoid(<h_i W_l, h_j W_r> / sqrt(32))``, all over the row's
  true length and chain split.

``quant="fp8"`` is the control: the same forward with every linear
layer's input and weight rounded to float8 e4m3 after per-tensor absmax
scaling -- the precision step below the bfloat16 the configuration states.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
FEAT = 16          # backbone / target descriptor width
PAE_RANK = 32      # pAE head projection width


def padded_vocab(m: dict) -> int:
    return 128 * math.ceil(int(m["vocab_size"]) / 128)


# -- weights -----------------------------------------------------------------


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)


def _stack_params(key, m: dict) -> dict:
    n, d = int(m["n_layers"]), int(m["d_model"])
    H, KV, hd = int(m["n_heads"]), int(m["n_kv_heads"]), int(m["head_dim"])
    f, V = int(m["d_ff"]), padded_vocab(m)
    k = jax.random.split(key, 9)
    layer = {
        "norm1": {"scale": jnp.ones((n, d), jnp.float32)},
        "norm2": {"scale": jnp.ones((n, d), jnp.float32)},
        "attn": {"wq": _normal(k[0], (n, d, H, hd), d),
                 "wk": _normal(k[1], (n, d, KV, hd), d),
                 "wv": _normal(k[2], (n, d, KV, hd), d),
                 "wo": _normal(k[3], (n, H, hd, d), H * hd)},
        "mlp": {"wi": _normal(k[4], (n, d, f), d),
                "wg": _normal(k[5], (n, d, f), d),
                "wo": _normal(k[6], (n, f, d), f)},
    }
    return {"embedding": {"tok": _normal(k[7], (V, d), d)},
            "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
            "lm_head": {"w": _normal(k[8], (d, V), d)},
            "segments": [{"0_attn": layer}]}


def generator_params(key, m: dict) -> dict:
    k1, k2 = jax.random.split(key)
    p = _stack_params(k1, m)
    p["struct_proj"] = {"w": _normal(k2, (FEAT, int(m["d_model"])), FEAT)}
    return p


def scorer_params(key, m: dict) -> dict:
    k1, k2 = jax.random.split(key)
    d = int(m["d_model"])
    p = _stack_params(k1, m)
    k = jax.random.split(k2, 5)
    p["heads"] = {"plddt": _normal(k[0], (d, 1), d),
                  "ptm": _normal(k[1], (d, 1), d),
                  "pae_l": _normal(k[2], (d, PAE_RANK), d),
                  "pae_r": _normal(k[3], (d, PAE_RANK), d),
                  "tgt": _normal(k[4], (FEAT, d), FEAT)}
    return p


# -- forward -------------------------------------------------------------------


def _q8(x):
    """Round to float8 e4m3 after per-tensor absmax scaling."""
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _lin(eq, x, w, quant):
    if quant == "fp8":
        x, w = _q8(x), _q8(w)
    return jnp.einsum(eq, x, w.astype(jnp.float32), precision=HI)


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * scale


def _rope(x, pos, theta):
    """Half-rotation RoPE over the whole head. x (B, S, H, hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv          # (S, hd/2)
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _decoder(seg, x, m, quant):
    """The causal decoder stack over x (B, S, d), float32."""
    H, KV = int(m["n_heads"]), int(m["n_kv_heads"])
    eps, theta = float(m["norm_eps"]), float(m["rope_theta"])
    B, S, _ = x.shape
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]                 # (q, k)
    for i in range(int(m["n_layers"])):
        p = jax.tree.map(lambda a: a[i], seg)
        h = _rms(x, p["norm1"]["scale"], eps)
        q = _rope(_lin("bsd,dhk->bshk", h, p["attn"]["wq"], quant), pos,
                  theta)
        k = _rope(_lin("bsd,dhk->bshk", h, p["attn"]["wk"], quant), pos,
                  theta)
        v = _lin("bsd,dhk->bshk", h, p["attn"]["wv"], quant)
        hd = q.shape[-1]
        q = q.reshape(B, S, KV, H // KV, hd)
        s = jnp.einsum("bqkgh,bskh->bkgqs", q, k, precision=HI) / np.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskh->bqkgh", w, v, precision=HI)
        x = x + _lin("bshk,hkd->bsd", o.reshape(B, S, H, hd),
                     p["attn"]["wo"], quant)
        h = _rms(x, p["norm2"]["scale"], eps)
        a = _lin("bsd,df->bsf", h, p["mlp"]["wi"], quant)
        g = _lin("bsd,df->bsf", h, p["mlp"]["wg"], quant)
        x = x + _lin("bsf,fd->bsd", jax.nn.silu(g) * a, p["mlp"]["wo"],
                     quant)
    return x


@partial(jax.jit, static_argnames=("m", "quant"))
def token_logprobs(params, backbones, tokens, *, m, quant=None):
    """Log-probability of each token given the structure prefix and the
    tokens before it. backbones (B, >=prefix, 16); tokens (B, T) int.
    Returns (B, T) float32. ``m`` is the generator's sizes as a tuple of
    (key, value) pairs."""
    m = dict(m)
    P = int(m["frontend_seq"])
    B, T = tokens.shape
    patches = _lin("bpf,fd->bpd", backbones[:, :P], params["struct_proj"]["w"],
                   quant)
    prev = jnp.concatenate([jnp.zeros((B, 1), tokens.dtype), tokens[:, :-1]],
                           1)
    x = jnp.concatenate([patches, params["embedding"]["tok"][prev]], 1)
    x = _decoder(params["segments"][0]["0_attn"], x, m, quant)
    x = _rms(x[:, P:], params["final_norm"]["scale"], float(m["norm_eps"]))
    logits = _lin("btd,dv->btv", x, params["lm_head"]["w"], quant)
    logp = jax.nn.log_softmax(logits[..., :int(m["vocab_size"])], -1)
    return jnp.take_along_axis(logp, tokens[..., None], -1)[..., 0]


@partial(jax.jit, static_argnames=("m", "quant"))
def fold_metrics(params, seqs, targets, seq_lens, splits, *, m, quant=None):
    """pLDDT, pTM, pAE of each row over its true length and chain split.
    seqs (B, L) int padded past seq_lens; targets (B, 16). Returns (B, 3)."""
    m = dict(m)
    hp = params["heads"]
    x = params["embedding"]["tok"][seqs]
    x = x + _lin("bf,fd->bd", targets, hp["tgt"], quant)[:, None]
    x = _decoder(params["segments"][0]["0_attn"], x, m, quant)
    x = _rms(x, params["final_norm"]["scale"], float(m["norm_eps"]))
    pos = jnp.arange(seqs.shape[1])[None]
    valid = (pos < seq_lens[:, None]).astype(jnp.float32)
    n = valid.sum(-1)
    plddt = (100.0 * jax.nn.sigmoid(_lin("bld,d->bl", x, hp["plddt"][:, 0],
                                         quant)) * valid).sum(-1) / n
    ptm = jax.nn.sigmoid((_lin("bld,d->bl", x, hp["ptm"][:, 0], quant)
                          * valid).sum(-1) / n)
    zl = _lin("bld,dk->blk", x, hp["pae_l"], quant)
    zr = _lin("bld,dk->blk", x, hp["pae_r"], quant)
    pae = 30.0 * jax.nn.sigmoid(
        jnp.einsum("bik,bjk->bij", zl, zr, precision=HI) / np.sqrt(PAE_RANK))
    rec = (pos < splits[:, None]).astype(jnp.float32)
    pep = valid * (1.0 - rec)
    den = rec.sum(-1) * pep.sum(-1)
    rp = jnp.einsum("bij,bi,bj->b", pae, rec, pep, precision=HI) / den
    pr = jnp.einsum("bij,bi,bj->b", pae, pep, rec, precision=HI) / den
    return jnp.stack([plddt, ptm, 0.5 * (rp + pr)], -1)
