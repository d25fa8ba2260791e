"""Compile rehearsals for a TPU v5e that is described, not attached: the
main path's device programs at their configured widths must compile for
the chip. The paged decode engine step (Pallas kernel) at ``progen-s``
widths, the masked predict step at ``foldscore-m`` widths, and the
finetune train step on one chip and data-parallel over four.

Nothing runs: shapes come from ``jax.eval_shape`` and no array is placed
on a described device. ``interpret=False`` is passed explicitly, because
``jax.default_backend()`` is still the CPU here. The topology is described
in a module fixture (skipping where it cannot be), and XLA's persistent
compilation cache is off around these compiles: what they would write
cannot be read back without a chip."""

import functools
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.configs.registry import get_config
from repro.core.payload import FinetunePayload
from repro.models import protein as prot
from repro.optim import init_opt_state

SLOTS, MAX_NEW = 16, 144        # decode slots; longest sampled length


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("page_size", [8, 16])
def test_paged_decode_step_compiles(one_chip, page_size):
    """The engine's own step program (paged decode attention through the
    Pallas kernel, sampling, the slots' advance and retirement) for 16
    slots of 144 tokens, with every per-slot array as donated state."""
    cfg = get_config("progen-s")
    eng = prot.PagedDecodeEngine(cfg, slots=SLOTS, max_new=MAX_NEW,
                                 page_size=page_size, interpret=False)
    params = _shapes(jax.eval_shape(
        lambda: prot.init_progen(jax.random.PRNGKey(0), cfg)), one_chip)
    state = _shapes(eng.state, one_chip)
    temp = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    compiled = eng._step_fn.lower(params, state, temp).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    aliases = next(line for line in text.splitlines()
                   if "input_output_alias" in line)
    assert aliases.count("may-alias") == len(jax.tree.leaves(eng.state))
    assert eng.trace_counts["step"] == 1


def test_paged_kernel_op_name(one_chip):
    """The Pallas kernel's own ``name=`` names its op in the compiled
    program, whatever function wraps it: the benchmark's trace readers
    find the kernel by ``_paged_decode_attention``."""
    from repro.kernels import paged_attention as pa
    B, KV, G, hd, page, maxp, P = 4, 2, 2, 32, 8, 3, 13
    f = jax.jit(lambda q, k, v, bt, ln: pa.paged_decode_bkgh(
        q, k, v, bt, ln, page_size=page, interpret=False))
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)
    text = f.lower(spec((B, KV, G, hd), jnp.bfloat16),
                   spec((P, KV, page, hd), jnp.bfloat16),
                   spec((P, KV, page, hd), jnp.bfloat16),
                   spec((B, maxp), jnp.int32),
                   spec((B,), jnp.int32)).compile().as_text()
    ops = [line.split(" = ", 1)[0].split()[-1] for line in text.splitlines()
           if "custom-call(" in line and "tpu_custom_call" in line]
    assert ops and all(o.startswith("%_paged_decode_attention")
                       for o in ops), ops


def test_masked_predict_compiles(one_chip):
    """``foldscore_fwd_masked`` at ``foldscore-m`` widths: 8 rows padded
    to the 144-token bucket, per-row lengths and chain splits traced."""
    cfg = get_config("foldscore-m")
    params = _shapes(jax.eval_shape(
        lambda: prot.init_foldscore(jax.random.PRNGKey(0), cfg)), one_chip)
    rows, L = 8, 144

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fwd = jax.jit(functools.partial(prot.foldscore_fwd_masked, cfg=cfg))
    compiled = fwd.lower(params, arg((rows, L), jnp.int32),
                         arg((rows, 16), jnp.float32),
                         arg((rows,), jnp.int32),
                         arg((rows,), jnp.int32)).compile()
    out = compiled.out_info
    assert out.plddt.shape == out.ptm.shape == out.pae.shape == (rows,)


@pytest.mark.parametrize("n_devices", [1, 4])
def test_finetune_step_compiles(topo, n_devices):
    """``FinetunePayload``'s train step at ``progen-s`` widths with the
    shardings ``finetune`` gives it: params and optimizer state
    replicated, the design batch split over the sub-mesh's rows. Across
    four chips the gradient needs an all-reduce."""
    cfg = get_config("progen-s")
    fp = FinetunePayload(types.SimpleNamespace(gen_cfg=cfg,
                                               param_store=None))
    mesh = Mesh(np.asarray(topo.devices[:n_devices]), ("sub",))
    repl = NamedSharding(mesh, PartitionSpec())
    rows = NamedSharding(mesh, PartitionSpec(tuple(mesh.axis_names)))
    params = jax.eval_shape(
        lambda: prot.init_progen(jax.random.PRNGKey(0), cfg))
    opt_state = jax.eval_shape(lambda: init_opt_state(params, fp.opt))
    B, L = 8, 96
    batch = {"backbones": jax.ShapeDtypeStruct(
                 (B, cfg.frontend_seq, 16), jnp.float32, sharding=rows),
             "sequences": jax.ShapeDtypeStruct((B, L), jnp.int32,
                                               sharding=rows),
             "weights": jax.ShapeDtypeStruct((B,), jnp.float32,
                                             sharding=rows)}
    compiled = fp._train_step().lower(
        _shapes(params, repl), _shapes(opt_state, repl), batch).compile()
    assert ("all-reduce" in compiled.as_text()) is (n_devices > 1)
