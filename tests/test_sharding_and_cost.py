"""Sharding rule engine + HLO cost parser tests (and hypothesis properties
for the recurrence chunking invariants)."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # dev extra (requirements-dev.txt): skip properties only
    from conftest import given, settings, st  # noqa: F401

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_config, get_reduced
from repro.distributed import sharding as shd
from repro.distributed.hlo_cost import analyze
from repro.models import ssm
from repro.kernels import ref


def one_dev_mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def test_param_rules_divisibility_fallback():
    mesh = one_dev_mesh()  # model axis size 1 divides everything
    cfg = get_config("llama3-8b")
    spec = shd.param_spec("segments/0/0_attn/wq", (32, 4096, 32, 128),
                          mesh, cfg)
    assert spec == P(None, ("data",), ("model",), None)
    # 15 heads on a 16-wide model axis would not divide -> replicated there
    import dataclasses
    mesh16 = Mesh(np.array([jax.devices()[0]] * 1).reshape(1, 1),
                  ("data", "model"))
    # emulate divisibility logic directly
    assert shd._fit(15, ("model",), mesh16) == ("model",)  # size-1 axis fits
    assert shd._fit(15, None, mesh16) is None


def test_cache_spec_kv_fallback_to_head_dim():
    """kv=8 vs model axis 16 -> shard head_dim instead (synthetic mesh via
    monkeypatched axis sizes)."""
    cfg = get_config("llama3-8b")

    class M:  # minimal mesh stub
        axis_names = ("data", "model")
        devices = np.empty((16, 16), dtype=object)

    # PartitionSpec normalises a one-axis tuple entry to the bare name
    spec = shd.cache_spec("segments/0/0_attn/k", (32, 128, 32768, 8, 128),
                          M(), cfg)
    assert spec[-2] is None and spec[-1] == "model"
    spec2 = shd.cache_spec("segments/0/0_attn/k", (32, 128, 32768, 16, 128),
                           M(), cfg)
    assert spec2[-2] == "model"


def test_constrain_is_noop_without_context():
    x = jnp.ones((4, 4))
    assert shd.constrain(x, ("batch", None)) is x


def test_tokens_sharding_divisibility():
    class M:
        axis_names = ("data", "model")
        devices = np.empty((16, 16), dtype=object)

    # divisibility logic (16-wide data axis): batch=1 must not shard
    assert shd._fit(1, ("data",), M()) is None
    assert shd._fit(128, ("data",), M()) == ("data",)
    # on a 1-wide mesh everything divides
    mesh = one_dev_mesh()
    sh = shd.tokens_sharding(mesh, (1, 128))
    assert sh.spec in (P(("data",)), P("data"))


# ---------------------------------------------------------------------------
# HLO cost parser
# ---------------------------------------------------------------------------

def test_scan_trip_count_multiplication():
    def f(x):
        def body(c, _):
            return jnp.tanh(c @ c), None
        return jax.lax.scan(body, x, None, length=9)[0]

    txt = jax.jit(f).lower(
        jax.ShapeDtypeStruct((32, 32), jnp.float32)).compile().as_text()
    c = analyze(txt)
    expect = 9 * (2 * 32 ** 3)
    assert abs(c.flops - expect) / expect < 0.05


def test_scanned_equals_unrolled():
    def fs(x):
        def body(c, _):
            return jnp.tanh(c @ c), None
        return jax.lax.scan(body, x, None, length=5)[0]

    def fu(x):
        for _ in range(5):
            x = jnp.tanh(x @ x)
        return x

    s = jax.ShapeDtypeStruct((48, 48), jnp.float32)
    cs = analyze(jax.jit(fs).lower(s).compile().as_text())
    cu = analyze(jax.jit(fu).lower(s).compile().as_text())
    assert abs(cs.flops - cu.flops) / cu.flops < 0.02


def test_collective_bytes_detected():
    import os
    mesh = one_dev_mesh()  # 1 device: collectives may fold away; use psum trick

    def g(x):
        return x @ x

    txt = jax.jit(g).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile().as_text()
    c = analyze(txt)
    assert c.flops >= 2 * 64 ** 3
    assert c.coll_total == 0  # no collectives on 1 device


def test_tagged_attribution():
    def f(x):
        with jax.named_scope("hotspot"):
            y = jnp.tanh(x @ x)
        return y + 1

    txt = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile().as_text()
    total, tagged = analyze(txt, tag_re="hotspot")
    assert tagged.flops >= 2 * 64 ** 3
    assert tagged.flops < total.flops


# ---------------------------------------------------------------------------
# hypothesis: chunking invariance of the recurrences
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(st.integers(1, 5), st.integers(1, 64), st.integers(0, 2 ** 31 - 1))
def test_wkv6_chunk_invariance(chunk, T, seed):
    """Property: the chunked WKV scan result is independent of chunk size and
    equals the sequential oracle for any (chunk, T)."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    B, H, K = 1, 2, 8
    r = 0.5 * jax.random.normal(ks[0], (B, H, T, K))
    k = 0.5 * jax.random.normal(ks[1], (B, H, T, K))
    v = 0.5 * jax.random.normal(ks[2], (B, H, T, K))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, H, T, K)))
    u = 0.3 * jnp.ones((H, K))
    s0 = jnp.zeros((B, H, K, K))
    y1, s1 = ssm.wkv6_chunked(r, k, v, logw, u, s0, chunk=chunk)
    y2, s2 = ref.wkv6_ref(r, k, v, logw, u, s0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=5e-5, rtol=5e-4)


@settings(max_examples=12, deadline=None)
@given(st.integers(1, 7), st.integers(1, 50), st.integers(0, 2 ** 31 - 1))
def test_rglru_chunk_invariance(chunk, T, seed):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    B, C = 2, 8
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, T, C)))
    b = 0.3 * jax.random.normal(ks[1], (B, T, C))
    h0 = jax.random.normal(ks[2], (B, C))
    h1, hT1 = ssm.rglru_scan(a, b, h0, chunk=chunk)
    h2, hT2 = ref.rglru_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               atol=2e-5, rtol=2e-4)
