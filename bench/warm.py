"""Warm-up: compile, before the window opens, every executable a cell's
traffic can reach, and no other. The payload task functions are called
directly with inputs of each shape on each device the cell uses, so the
program's per-(kind, device, shape) caches and the compile cache hold
them all when the window opens: each batched kind at every row bucket up
to its coalesce rule's ``max_rows`` and at each length bucket of the
traffic, and one paged engine per length bucket.
"""

from __future__ import annotations

import numpy as np

FEAT = 16


def submeshes(devices):
    """A one-device sub-mesh on each device."""
    from repro.runtime import DeviceAllocator
    return [DeviceAllocator([d]).request(1) for d in devices]


def row_buckets():
    """Every row bucket: the coalesce rules' default cap lets a fused
    batch reach any of them."""
    from repro.runtime.allocator import BATCH_BUCKETS
    return list(BATCH_BUCKETS)


def scorer(payload, subs, *, rows, lengths, peptide_len, params=None):
    """``predict_batch`` (masked) at every (row bucket, complex length)."""
    for sub in subs:
        for R in rows:
            for L in lengths:
                p = {"sequences": np.ones((R, L), np.int32),
                     "target": np.zeros(FEAT, np.float32),
                     "receptor_len": L - peptide_len,
                     "seq_lens": np.full(R, L, np.int32),
                     "chain_splits": np.full(R, L - peptide_len, np.int32)}
                if params:
                    p["params"] = params
                payload.predict_batch(sub, p)


def paged_generator(payload, subs, *, lengths, n, slots, prefix,
                    temperature=1.0, params=None):
    """One paged decode engine per sample-length bucket, per device: a
    one-row run compiles its admit and step programs."""
    for sub in subs:
        for L in lengths:
            p = {"backbones": np.zeros((1, prefix, FEAT), np.float32),
                 "seeds": [0], "n": n, "length": L, "row_lens": [L],
                 "temperature": temperature, "decode": "paged",
                 "decode_slots": slots}
            if params:
                p["params"] = params
            payload.generate_batch(sub, p)


def dense_generator(payload, subs, *, rows, lengths, n, prefix,
                    temperature=1.0, params=None):
    """Masked dense ``generate_batch`` at every (row bucket, length)."""
    for sub in subs:
        for R in rows:
            for L in lengths:
                p = {"backbones": np.zeros((R, prefix, FEAT), np.float32),
                     "seeds": np.arange(R), "n": n, "length": L,
                     "row_lens": np.full(R, L, np.int32),
                     "temperature": temperature}
                if params:
                    p["params"] = params
                payload.generate_batch(sub, p)


def backbones(payload, subs, *, rows, widths, m, sigma):
    """``backbone_batch`` at every (row bucket, backbone width)."""
    for sub in subs:
        for R in rows:
            for P in widths:
                payload.backbone_batch(sub, {
                    "bases": np.zeros((R, P, FEAT), np.float32),
                    "targets": np.zeros((R, FEAT), np.float32),
                    "seeds": np.arange(R), "m": m, "sigma": sigma})
