"""Blocked online-softmax (flash) attention — Pallas TPU kernel.

Grid: (batch, q_head, q_blocks, k_blocks); the k-block axis is innermost and
sequential — running max / denominator / accumulator live in VMEM scratch
and are carried across k blocks (reset at ik==0, emitted at the last block).

GQA is handled in the k/v BlockSpec index maps (kv_head = q_head // group),
so no head replication ever materializes. Causal and local-window masking
skip fully-masked k blocks via ``pl.when`` — for causal attention this
halves the work; for a local window the work per q block is O(window).

Block shapes default to (128, 128): MXU-aligned (q·kᵀ is a 128×hd×128
matmul) and small enough that q/k/v/acc tiles fit VMEM comfortably
(4 tiles × 128 × hd(≤256) × 4B ≈ 0.5 MB).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG = -0.7 * float(np.finfo(np.float32).max)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal, window, softcap, block_q, block_k, seq_q, seq_k, scale):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    # static-shape block skip conditions (dynamic on grid ids)
    live = k_start < seq_k
    if causal:
        live &= k_start <= q_start + block_q - 1
    if window > 0:
        live &= k_start + block_k - 1 > q_start - window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)              # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = (cols < seq_k) & (rows < seq_q)
        if causal:
            mask &= cols <= rows
        if window > 0:
            mask &= cols > rows - window
        s = jnp.where(mask, s, NEG)
        m_prev = m_ref[...][:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None]) * mask
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = (l_ref[...][:, 0] * alpha + p.sum(-1))[:, None]
        acc_ref[...] = acc_ref[...] * alpha[:, None] + \
            jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new[:, None]

    @pl.when(ik == pl.num_programs(3) - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal=True, window=0, softcap=0.0,
                         block_q=128, block_k=128, seq_q=None, seq_k=None,
                         interpret=False):
    """q (B,H,Sq,hd); k/v (B,KV,Sk,hd), Sq/Sk already padded to block
    multiples; seq_q/seq_k are the pre-padding lengths for masking."""
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    group = H // KV
    seq_q = seq_q or Sq
    seq_k = seq_k or Sk
    grid = (B, H, Sq // block_q, Sk // block_k)
    kern = functools.partial(
        _kernel, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, seq_q=seq_q, seq_k=seq_k,
        scale=1.0 / np.sqrt(hd))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
