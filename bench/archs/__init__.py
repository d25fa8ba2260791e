"""Architectures of the payload models, one module each, found by name:
``bench/archs/<name>.py``. A model entry of a configuration file names
its architecture with ``"arch"``; without the key it is ``"dense"``.

``m`` below is a model's sizes as the configuration states them, with
the configuration's ``architecture`` keys added. A module exports:

- ``params(key, m, kind)``: the model's float32 weights in the program's
  parameter layout, for ``kind`` ``"generator"`` or ``"scorer"``;
- for generators, ``token_logprobs(params, backbones, tokens, *, m,
  quant=None)`` -> (B, T) log-probabilities; for scorers,
  ``fold_metrics(params, seqs, targets, seq_lens, splits, *, m,
  quant=None)`` -> (B, 3) pLDDT, pTM, pAE. These are the plain float32
  reference at ``precision="highest"``, importing nothing of the program,
  and with ``quant="fp8"`` the control, one precision step below the
  bfloat16 the configurations state. ``m`` arrives as a sorted tuple of
  (key, value) pairs, so a jitted reference can take it as static;
- ``generator_flops(m, tokens)`` and ``scorer_flops(m, L)``: the useful
  operations of sampling ``tokens`` tokens on one structure and of
  scoring one complex of ``L`` residues (``payload_mfu``);
- ``scorer_call(m, rows, L)``: (operations, bytes) of one scorer
  executable over a padded (rows, L) batch (the scorer's roofline);
- ``step_counts(m, kv_lens)``: ``{counted name: (operations, bytes)}`` of
  one paged decode step whose rows hold ``kv_lens`` valid K/V entries
  (0 for an empty slot), one entry per kernel of the step that has a
  roofline metric. A reader ``bench/metrics/<kernel>_roofline.py`` takes
  the share with ``readers.roofline(ctx, <counted name>, "op_time",
  <op-name patterns>)``.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT = "dense"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_loaded = {}


def name_of(model: dict) -> str:
    """The architecture a configuration's model entry names."""
    return model.get("arch", DEFAULT)


def load(name: str, bench_dir: str = BENCH):
    """The module ``<bench_dir>/archs/<name>.py``, loaded once per path so
    that its jitted functions keep one compile cache."""
    from bench.harness import load_module
    path = os.path.join(bench_dir, "archs", f"{name}.py")
    if path not in _loaded:
        _loaded[path] = load_module(
            path, "bench_arch_" + name.replace(".", "_").replace("-", "_"))
    return _loaded[path]


def make_weights(key_words, models: dict, device=None) -> dict:
    """Every model of a configuration, ``{role: params}``, from a raw
    (2,) uint32 key in one jitted call on ``device``. ``models`` maps each
    role to its (architecture module, kind, sizes)."""
    roles = sorted(models)

    def build(key):
        keys = jax.random.split(key, len(roles))
        return {r: models[r][0].params(k, models[r][2], models[r][1])
                for r, k in zip(roles, keys)}

    key = jnp.asarray(np.asarray(key_words, np.uint32))
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(build)(key)
