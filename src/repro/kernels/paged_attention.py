"""Pallas single-token decode attention over a paged KV cache.

One query token per row attends to that row's K/V history, which lives in
fixed-size *pages* of a shared pool (the vLLM / maxtext ``ragged_mqa``
layout). A per-row *block table* maps logical page index -> physical page
id, and a per-row ``length`` gives the number of valid K/V entries, so:

  * rows of different true lengths share one dense launch — a padded or
    short row costs no attention FLOPs past its last live page (the grid
    step over a dead page is skipped with ``pl.when``);
  * admitting a new row or retiring a finished one only rewrites its
    block-table row and length on the host — the page buffers never
    change shape, so a warm decode loop never recompiles or copies cache.

Grid: (rows, max_pages_per_row). The page axis is sequential; an online
softmax accumulates (m, l, acc) in VMEM scratch across a row's pages and
emits once at the last page. ``lengths[b] == 0`` marks an inactive slot:
no page is ever live, l stays 0 and the output row is exactly zero (its
block table points at a trash page, so its cache writes are harmless).

Layouts (head-major, like flash_attention_bhsd):
  q           (B, KV, G, hd)      one query token per row, grouped heads
  k/v_pages   (P, KV, page, hd)   shared page pool (P includes trash page)
  block_table (B, maxp) int32     physical page id per logical page
  lengths     (B,) int32          valid K/V entries per row (0 = inactive)

``interpret=True`` runs the kernel body under the Pallas interpreter on
CPU (tests/CI); the compiled path sets TPU dimension semantics
("parallel" rows, "arbitrary" sequential page axis). The interpreter
executes grid cells *sequentially*, so a production decode loop on a
non-TPU backend should use ``paged_decode_ref`` instead — the same
contract as one vectorized gather + masked softmax over all rows at
once (``ops.paged_decode_attention`` does this dispatch); parity
between the two is pinned in tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -0.7 * float(np.finfo(np.float32).max)


def paged_decode_ref(q, k_pages, v_pages, block_tables, lengths, *,
                     page_size: int):
    """Vectorized jnp twin of ``paged_decode_bkgh`` (same signature minus
    ``interpret``, same fp32 softmax accumulation, same zero output for
    ``lengths[b] == 0``). One batched page gather + masked softmax over
    every row at once — the fallback non-TPU backends decode with, since
    interpreting the Pallas grid serializes over rows."""
    B, KV, G, hd = q.shape
    maxp = block_tables.shape[1]
    T = maxp * page_size
    # (B, maxp, KV, page, hd) -> (B, KV, maxp*page, hd)
    k = jnp.take(k_pages, block_tables, axis=0)
    v = jnp.take(v_pages, block_tables, axis=0)
    k = k.transpose(0, 2, 1, 3, 4).reshape(B, KV, T, hd).astype(jnp.float32)
    v = v.transpose(0, 2, 1, 3, 4).reshape(B, KV, T, hd).astype(jnp.float32)
    qf = q.astype(jnp.float32) * (1.0 / np.sqrt(hd))
    # broadcast-multiply + reduce instead of einsum: the (B*KV, G, T)
    # batched dot lowers to B*KV tiny GEMM instances on CPU whose
    # per-instance overhead dominates at decode sizes; one fused
    # vectorized reduction is ~2x faster at 64 rows
    s = (qf[:, :, :, None, :] * k[:, :, None, :, :]).sum(-1)  # (B,KV,G,T)
    mask = jnp.arange(T, dtype=jnp.int32)[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m) * mask[:, None, None, :]
    l = jnp.maximum(p.sum(-1), 1e-20)     # inactive rows: l=0 -> out=0
    out = (p[..., None] * v[:, :, None, :, :]).sum(-2) / l[..., None]
    return out.astype(q.dtype)


def _decode_kernel(bt_ref, tl_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, page_size, scale):
    b, ip = pl.program_id(0), pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    tl = tl_ref[b]
    live = ip * page_size < tl          # dead pages cost nothing

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                    # (KV, G, hd)
        k = k_ref[0].astype(jnp.float32)                    # (KV, page, hd)
        v = v_ref[0].astype(jnp.float32)
        # (KV, G, hd) x (KV, page, hd) -> (KV, G, page)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,)))) * scale
        cols = ip * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        mask = cols < tl                # tail of the last live page
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None]) * mask
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1)
        acc_ref[...] = acc_ref[...] * alpha[..., None] + \
            jax.lax.dot_general(p, v, (((2,), (1,)), ((0,), (0,))))
        m_ref[...] = m_new

    @pl.when(ip == pl.num_programs(1) - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-20)   # inactive rows: l=0 -> out=0
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def paged_decode_bkgh(q, k_pages, v_pages, block_tables, lengths, *,
                      page_size: int, interpret: bool = False):
    """q (B, KV, G, hd); k/v_pages (P, KV, page_size, hd); block_tables
    (B, maxp) i32; lengths (B,) i32. Returns (B, KV, G, hd)."""
    B, KV, G, hd = q.shape
    maxp = block_tables.shape[1]
    kern = functools.partial(_decode_kernel, page_size=page_size,
                             scale=1.0 / np.sqrt(hd))
    # block tables + lengths ride as scalar-prefetch operands: the index
    # maps read them to steer which physical page each grid step loads
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, maxp),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd), lambda b, ip, bt, tl: (b, 0, 0, 0)),
            pl.BlockSpec((1, KV, page_size, hd),
                         lambda b, ip, bt, tl: (bt[b, ip], 0, 0, 0)),
            pl.BlockSpec((1, KV, page_size, hd),
                         lambda b, ip, bt, tl: (bt[b, ip], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, G, hd),
                               lambda b, ip, bt, tl: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G), jnp.float32),       # m
            pltpu.VMEM((KV, G), jnp.float32),       # l
            pltpu.VMEM((KV, G, hd), jnp.float32),   # acc
        ],
    )
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
        **kwargs,
    )(block_tables, lengths, q, k_pages, v_pages)
