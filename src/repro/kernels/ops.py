"""Jit'd public wrappers around the Pallas kernels: layout conversion,
padding to block multiples, and implementation dispatch.

Model code calls these with model-layout tensors; the wrappers convert to
kernel layout, pad sequence dims, invoke the kernel (TPU-compiled or
interpret-on-CPU), and slice the padding back off.

Every wrapper takes ``interpret=None`` meaning *auto*: interpret mode on
any non-TPU backend, the compiled kernel on a TPU (see
``_compat.resolve_interpret``). Resolution happens in the un-jitted
wrapper — before tracing — so the flag is a plain static argument of the
inner jitted function.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _pa
from repro.kernels import rglru as _rg
from repro.kernels import rwkv6 as _wk
from repro.kernels._compat import resolve_interpret


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "block_q", "block_k", "interpret"))
def _flash_attention(q, k, v, *, causal, window, softcap, block_q, block_k,
                     interpret):
    B, S, H, hd = q.shape
    T = k.shape[1]
    qt = _pad_to(q.transpose(0, 2, 1, 3), 2, block_q)
    kt = _pad_to(k.transpose(0, 2, 1, 3), 2, block_k)
    vt = _pad_to(v.transpose(0, 2, 1, 3), 2, block_k)
    out = _fa.flash_attention_bhsd(
        qt, kt, vt, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, seq_q=S, seq_k=T,
        interpret=interpret)
    return out[:, :, :S].transpose(0, 2, 1, 3)


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    block_q=128, block_k=128, interpret=None):
    """Model layout: q (B,S,H,hd); k/v (B,T,KV,hd). Returns (B,S,H,hd)."""
    return _flash_attention(q, k, v, causal=causal, window=window,
                            softcap=softcap, block_q=block_q, block_k=block_k,
                            interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def _paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                            page_size, interpret):
    B, S, H, hd = q.shape
    KV = k_pages.shape[1]
    qk = q[:, 0].reshape(B, KV, H // KV, hd)    # h = kv * G + g grouping
    if interpret:
        # interpreting the Pallas grid runs its cells sequentially —
        # O(rows) per step — so non-TPU backends decode through the
        # vectorized twin instead (parity pinned in tests)
        out = _pa.paged_decode_ref(qk, k_pages, v_pages, block_tables,
                                   lengths, page_size=page_size)
    else:
        out = _pa.paged_decode_bkgh(qk, k_pages, v_pages, block_tables,
                                    lengths, page_size=page_size,
                                    interpret=False)
    return out.reshape(B, 1, H, hd)


def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           page_size, interpret=None):
    """Single-token decode over a paged KV cache.

    Model layout: q (B,1,H,hd); k/v_pages (P,KV,page_size,hd);
    block_tables (B,maxp) i32; lengths (B,) i32 valid entries per row
    (0 = inactive slot, output row is zero). Returns (B,1,H,hd)."""
    return _paged_decode_attention(q, k_pages, v_pages, block_tables,
                                   lengths, page_size=page_size,
                                   interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _wkv6(r, k, v, logw, u, s0, *, chunk, interpret):
    T = r.shape[2]
    chunk = min(chunk, T)
    while T % chunk:
        chunk -= 1
    # pad with identity steps (logw=0 -> decay=1, k=v=r=0) if ever needed
    return _wk.wkv6_bhtk(r, k, v, logw.astype(jnp.float32), u,
                         s0.astype(jnp.float32), chunk=chunk,
                         interpret=interpret)


def wkv6(r, k, v, logw, u, s0, *, chunk=32, interpret=None):
    """r/k/v/logw (B,H,T,K); u (H,K); s0 (B,H,K,K).
    Returns y (B,H,T,K), s_T (B,H,K,K) fp32."""
    return _wkv6(r, k, v, logw, u, s0, chunk=chunk,
                 interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_t", "block_c",
                                             "interpret"))
def _rglru(a, b, h0, *, block_t, block_c, interpret):
    B, T, C = a.shape
    bt = min(block_t, T)
    while T % bt:
        bt -= 1
    bc = min(block_c, C)
    while C % bc:
        bc -= 1
    return _rg.rglru_btc(a.astype(jnp.float32), b.astype(jnp.float32),
                         h0.astype(jnp.float32), block_t=bt, block_c=bc,
                         interpret=interpret)


def rglru(a, b, h0, *, block_t=256, block_c=128, interpret=None):
    """a/b (B,T,C) f32; h0 (B,C). Returns h (B,T,C) f32, h_T (B,C) f32."""
    return _rglru(a, b, h0, block_t=block_t, block_c=block_c,
                  interpret=resolve_interpret(interpret))
