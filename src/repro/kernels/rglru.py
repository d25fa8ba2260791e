"""RG-LRU gated linear recurrence — Pallas TPU kernel.

h_t = a_t ⊙ h_{t-1} + b_t, per channel. The projections/gates around the
recurrence are dense matmuls that XLA already handles; the recurrence itself
is the memory-bound hot-spot this kernel owns. Grid: (batch, channel-blocks,
time-blocks), time sequential; the channel axis is embarrassingly parallel
(TPU-native: channels map to VPU lanes, blocks of 128). Within a time block
the kernel runs the exact sequential FMA recurrence over the VMEM-resident
tile — bitwise-faithful to the oracle, one HBM round-trip per element.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h0_ref, h_ref, hT_ref, s_ref, *, block_t):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        s_ref[...] = h0_ref[...].astype(jnp.float32)

    def step(i, h):
        h = a_ref[0, i].astype(jnp.float32) * h + b_ref[0, i].astype(jnp.float32)
        h_ref[0, pl.dslice(i, 1), :] = h[None].astype(h_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_t, step, s_ref[0])
    s_ref[...] = h[None]

    @pl.when(it == pl.num_programs(2) - 1)
    def _emit():
        hT_ref[...] = h[None]


def rglru_btc(a, b, h0, *, block_t=256, block_c=128, interpret=False):
    """a/b (B,T,C) f32 with T % block_t == 0 == C % block_c; h0 (B,C) f32.
    Returns h (B,T,C) f32 and h_T (B,C) f32."""
    B, T, C = a.shape
    block_t = min(block_t, T)
    block_c = min(block_c, C)
    grid = (B, C // block_c, T // block_t)
    kern = functools.partial(_kernel, block_t=block_t)
    h, hT = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, block_c), lambda b, c, t: (b, t, c)),
            pl.BlockSpec((1, block_t, block_c), lambda b, c, t: (b, t, c)),
            pl.BlockSpec((1, block_c), lambda b, c, t: (b, c)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, block_c), lambda b, c, t: (b, t, c)),
            pl.BlockSpec((1, block_c), lambda b, c, t: (b, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, C), jnp.float32),
            jax.ShapeDtypeStruct((B, C), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, h0)
    return h, hT
