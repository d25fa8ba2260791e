"""Pallas single-token decode attention over a paged KV cache.

One query token per row attends to that row's K/V history, which lives in
fixed-size *pages* of a shared pool (the vLLM / maxtext ``ragged_mqa``
layout). A per-row *block table* maps logical page index -> physical page
id, and a per-row ``length`` gives the number of valid K/V entries, so:

  * rows of different true lengths share one dense launch — a padded or
    short row fetches and attends over its live pages only;
  * admitting a new row or retiring a finished one only rewrites its
    block-table row and length on the host — the page buffers never
    change shape, so a warm decode loop never recompiles or copies cache.

Grid: (rows, chunks). A grid step takes ``n = pages_per_step(...)`` of a
row's page positions, all of them while their blocks fit one step's VMEM
budget (one step per row in the decode engines that run today), else the
fewest equal chunks that do, walked in order with an online softmax
carried across them. The K and V pools are each passed once per page
position of a chunk, and position ``i``'s BlockSpec picks the row's
physical page ``block_table[b, c * n + i]`` while it is live and page 0
once it is dead; the pipeline copies a block only when its index changes
from one step to the next, so a page that stays dead from step to step is
never fetched, and the next step's pages are copied while this one
computes. A step lays its pages end to end per KV head and runs one masked
f32 softmax over them: scores past the length are masked and V rows past
it zeroed, since a dead page's block may hold anything, NaN included; a
chunk wholly past the length computes nothing. ``lengths[b] == 0`` marks
an inactive slot: l stays 0 and its output row is exactly zero (its block
table points at a trash page, so its cache writes are harmless). Pages are
gathered by BlockSpecs and not by hand-issued DMAs because Mosaic cannot
slice a page out of an HBM pool whose head dim is under 128 lanes.

Layouts (head-major, like flash_attention_bhsd):
  q           (B, KV, G, hd)      one query token per row, grouped heads
  k/v_pages   (P, KV, page, hd)   shared page pool (P includes trash page)
  block_table (B, maxp) int32     physical page id per logical page
  lengths     (B,) int32          valid K/V entries per row (0 = inactive)

``interpret=True`` runs the kernel body under the Pallas interpreter on
CPU (tests/CI). The interpreter executes grid cells *sequentially*, so a
production decode loop on a non-TPU backend should use
``paged_decode_ref`` instead — the same contract as one vectorized
gather + masked softmax over all rows at once
(``ops.paged_decode_attention`` does this dispatch); parity between the
two is pinned in tests.
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


# VMEM the page blocks of one grid step may take: K and V, double-buffered,
# at their tiled size; half of v5e's 16 MiB scoped VMEM, the rest left to
# the body's f32 copies of them
_PAGE_VMEM_BYTES = 8 << 20


def pages_per_step(maxp, KV, page_size, hd, dtype):
    """Page positions one grid step fetches: all ``maxp`` of a row while
    their blocks fit ``_PAGE_VMEM_BYTES``, else the fewest equal chunks
    that do. A block ``(1, KV, page_size, hd)`` is tiled in VMEM as
    (sublanes, 128 lanes), 8 sublanes of 32 bits each."""
    itemsize = np.dtype(dtype).itemsize
    sub = 8 * 4 // itemsize
    block = KV * -(-page_size // sub) * sub * -(-hd // 128) * 128 * itemsize
    cap = max(1, _PAGE_VMEM_BYTES // (4 * block))
    chunks = -(-maxp // cap)
    return -(-maxp // chunks)


def _decode_kernel(bt_ref, tl_ref, q_ref, *refs, page_size, scale):
    *kv_refs, o_ref, m_ref, l_ref, acc_ref = refs
    n = len(kv_refs) // 2
    k_refs, v_refs = kv_refs[:n], kv_refs[n:]
    tl = tl_ref[pl.program_id(0)]
    c = pl.program_id(1)
    T = n * page_size
    start = c * T

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(start < tl)                # chunks past the length cost nothing
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale            # (KV, G, hd)
        # a dead page's block is whatever page 0 holds (possibly NaN):
        # mask the scores and zero the V rows, as 0 * NaN in p @ v would
        # still be NaN
        cols = start + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1) < tl
        rows = start + jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0) < tl
        for h in range(q.shape[0]):
            # the chunk's pages of head h, end to end: (T, hd)
            k = jnp.concatenate([r[0, h].astype(jnp.float32)
                                 for r in k_refs], axis=0)
            v = jnp.concatenate([r[0, h].astype(jnp.float32)
                                 for r in v_refs], axis=0)
            v = jnp.where(rows, v, 0.0)
            # (G, hd) x (T, hd) -> (G, T); online softmax across chunks
            s = jax.lax.dot_general(q[h], k, (((1,), (1,)), ((), ())))
            s = jnp.where(cols, s, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + p.sum(axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(p, v)
            m_ref[h] = m_new

    @pl.when(c == pl.num_programs(1) - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-20)   # inactive rows: l=0 -> out=0
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_bkgh(q, k_pages, v_pages, block_tables, lengths, *,
                      page_size: int, interpret: bool = False):
    """q (B, KV, G, hd); k/v_pages (P, KV, page_size, hd); block_tables
    (B, maxp) i32; lengths (B,) i32. Returns (B, KV, G, hd)."""
    B, KV, G, hd = q.shape
    maxp = block_tables.shape[1]
    n = pages_per_step(maxp, KV, page_size, hd, k_pages.dtype)
    kern = functools.partial(_decode_kernel, page_size=page_size,
                             scale=1.0 / np.sqrt(hd))

    def page_spec(i):
        # block tables + lengths ride as scalar-prefetch operands: page
        # c * n + i of row b is its block-table entry while live, and page
        # 0 once dead or past maxp, so a page that stays dead from one step
        # to the next keeps its block and the pipeline copies nothing for it
        def index(b, c, bt, tl):
            j = c * n + i
            pid = bt[b, jnp.minimum(j, maxp - 1)]
            return (jnp.where(j * page_size < tl[b], pid, 0), 0, 0, 0)
        return pl.BlockSpec((1, KV, page_size, hd), index)

    pages = [page_spec(i) for i in range(n)]
    row = pl.BlockSpec((1, KV, G, hd), lambda b, c, bt, tl: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, -(-maxp // n)),
        in_specs=[row, *pages, *pages],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),    # m
            pltpu.VMEM((KV, G, 1), jnp.float32),    # l
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
        # the op name in a profile; bench/readers.py finds the kernel by it
        name="_paged_decode_attention",
        **kwargs,
    )(block_tables, lengths, q, *[k_pages] * n, *[v_pages] * n)
