"""Operations and bytes of the benchmark's kernels and models, from their
shapes. ``m`` is a model's sizes as the configuration file states them.

A multiply-add counts as two operations. Bytes are what the algorithm has
to move through HBM at the stated types: float32 weights (the parameter
type), bfloat16 activations and KV cache (the compute type).
"""

from __future__ import annotations

import math

BF16, F32 = 2, 4
FEAT, PAE_RANK = 16, 32


def layer_params(m) -> int:
    d, H, KV, hd, f = (int(m[k]) for k in
                       ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff"))
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f + 2 * d


def stack_flops(m, S: int) -> int:
    """A causal decoder stack over S positions: the linear layers and the
    causal attention (each query sees itself and the positions before)."""
    H, hd = int(m["n_heads"]), int(m["head_dim"])
    linear = 2 * (layer_params(m) - 2 * int(m["d_model"])) * S
    attn = 4 * H * hd * (S * (S + 1) // 2)      # QK^T and PV
    return int(m["n_layers"]) * (linear + attn)


def generator_flops(m, tokens: int) -> int:
    """Useful work of sampling ``tokens`` tokens on one structure: the
    structure projection, the stack over prefix + BOS + the tokens fed
    back, and the LM head over the real vocabulary at each sampled
    position."""
    P, d, V = int(m["frontend_seq"]), int(m["d_model"]), int(m["vocab_size"])
    return (2 * FEAT * d * P + stack_flops(m, P + tokens)
            + 2 * d * V * tokens)


def scorer_flops(m, L: int) -> int:
    """One scorer forward over a complex of L residues: target projection,
    the stack, the pLDDT/pTM heads and the full L x L pAE matrix."""
    d = int(m["d_model"])
    return (2 * FEAT * d + stack_flops(m, L) + 2 * 2 * d * L
            + 2 * 2 * d * PAE_RANK * L + 2 * PAE_RANK * L * L)


def scorer_params(m) -> int:
    d, V = int(m["d_model"]), 128 * math.ceil(int(m["vocab_size"]) / 128)
    return (int(m["n_layers"]) * layer_params(m) + 2 * V * d + d
            + 2 * d + 2 * d * PAE_RANK + FEAT * d)


def scorer_call(m, rows: int, L: int):
    """(operations, bytes) of one scorer executable over a (rows, L)
    batch, padding included: the work the executable is asked to do. The
    bytes are the float32 weights read once, the tokens and targets in,
    and the three metrics out."""
    flops = rows * scorer_flops(m, L)
    byts = (F32 * scorer_params(m) + rows * (4 * L + F32 * FEAT)
            + rows * 3 * F32)
    return flops, byts


def paged_decode_step(m, kv_lens):
    """(operations, bytes) of the paged decode attention kernel for one
    decode step, summed over the layers: for each active row with
    ``n`` valid K/V entries, QK^T and PV over those entries, reading
    their K and V once and the query and output once. Inactive rows
    (n == 0) cost nothing."""
    H, KV, hd = int(m["n_heads"]), int(m["n_kv_heads"]), int(m["head_dim"])
    live = [int(n) for n in kv_lens if n > 0]
    n_tot = sum(live)
    flops = 4 * H * hd * n_tot
    byts = 2 * KV * hd * BF16 * n_tot + len(live) * 2 * H * hd * BF16
    layers = int(m["n_layers"])
    return layers * flops, layers * byts


def roofline_s(flops: float, byts: float, peak: dict) -> float:
    """Least time the chip could take: the larger of operations over peak
    and bytes over HBM bandwidth."""
    return max(flops / float(peak["bf16_flops_per_s"]),
               byts / float(peak["hbm_bytes_per_s"]))
