"""RWKV-6 (Finch) chunked WKV scan — Pallas TPU kernel.

The recurrence  S_t = diag(w_t) S_{t-1} + k_t v_tᵀ ;  y_t = r_t·(S_{t-1} +
diag(u) k_t v_tᵀ)  is executed chunk-parallel: within a chunk of C tokens the
pairwise decays form a strictly-lower-triangular (C,C,K) tensor whose
exponents are all ≤ 0 (numerically stable by construction), so the intra-
chunk contribution is two MXU matmuls; the (K,V) state is carried across
chunks in VMEM scratch. Grid: (batch, head, time-chunks) with the chunk axis
sequential. This is the TPU-native adaptation of the CUDA wkv kernel: the
per-token serial loop becomes per-chunk matmuls sized to the MXU.

VMEM per step (C=32, K=64 fp32): r/k/v/w tiles 32 KB, D (C,C,K) 256 KB,
state 16 KB — well under budget; C can grow to 128 on real hardware.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref, s_ref,
            *, chunk):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)            # (C, K)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = w_ref[0, 0].astype(jnp.float32)           # log-decay, <= 0
    u = u_ref[0].astype(jnp.float32)               # (K,)
    S = s_ref[...]                                 # (K, V)

    cl = jnp.cumsum(lw, axis=0)                    # inclusive
    ecl = cl - lw                                  # exclusive
    # carry-in term
    rt = r * jnp.exp(ecl)
    y = jax.lax.dot_general(rt, S, (((1,), (0,)), ((), ())))
    # intra-chunk: D[t,j,:] = exp(ecl_t - cl_j) for j<t (exponent <= 0)
    D = jnp.exp(jnp.minimum(ecl[:, None, :] - cl[None, :, :], 0.0))
    scores = (r[:, None, :] * k[None, :, :] * D).sum(-1)      # (C, C)
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.where(tri, scores, 0.0)
    bonus = (r * u[None, :] * k).sum(-1)                      # (C,)
    y = y + jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())))
    y = y + bonus[:, None] * v
    # state update (exponents <= 0)
    kdec = k * jnp.exp(cl[-1:] - cl)
    S = S * jnp.exp(cl[-1])[:, None] + \
        jax.lax.dot_general(kdec, v, (((0,), (0,)), ((), ())))
    s_ref[...] = S
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(it == pl.num_programs(2) - 1)
    def _emit():
        sT_ref[0, 0] = S


def wkv6_bhtk(r, k, v, logw, u, s0, *, chunk=32, interpret=False):
    """r/k/v/logw (B,H,T,K) with T % chunk == 0; u (H,K); s0 (B,H,K,K) f32.
    Returns y (B,H,T,K) in r.dtype and s_T (B,H,K,K) f32."""
    B, H, T, K = r.shape
    grid = (B, H, T // chunk)
    kern = functools.partial(_kernel, chunk=chunk)
    y, sT = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, K), lambda b, h, t: (h, 0)),
            pl.BlockSpec((1, 1, K, K), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, K, K), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, K), r.dtype),
            jax.ShapeDtypeStruct((B, H, K, K), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, K), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, logw, u, s0)
    return y, sT
