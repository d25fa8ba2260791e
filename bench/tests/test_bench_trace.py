"""The trace reduction on a small excerpt of a trace recorded on a TPU v5e
(``data/trace_excerpt.json.gz``: 3,000 consecutive operation events of
device 0 in a traced run of ``imrp-paged-mixlen``, the executables and
host events around them), against counts made here a second way."""

import gzip
import json
import os

import numpy as np
import pytest

from bench import readers, trace

from bench.tests.conftest import HERE


@pytest.fixture(scope="module")
def excerpt():
    with gzip.open(os.path.join(HERE, "data", "trace_excerpt.json.gz"),
                   "rt") as f:
        ex = json.load(f)
    devices = {d: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
               for d, lines in ex["devices"].items()}
    return devices, [tuple(h) for h in ex["host"]]


def _busy_by_sweep(events):
    """Busy nanoseconds by a boundary sweep: +1 at each start, -1 at each
    end; time counts where at least one op runs."""
    marks = sorted([(s, 1) for _, s, _ in events]
                   + [(s + d, -1) for _, s, d in events])
    busy, depth, last = 0, 0, None
    for t, step in marks:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_busy_and_idle(excerpt):
    devices, host = excerpt
    (name, lines), = devices.items()
    ops = lines[trace.OPS_LINE]
    red = trace.reduce_planes(devices, host, n_devices=1)
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(_busy_by_sweep(ops) * 1e-9,
                                          abs=1e-9)
    span = (max(s + d for _, s, d in ops) - min(s for _, s, _ in ops)) * 1e-9
    idle = span - red["busy_s"]
    assert 0 < red["busy_s"] < span
    gaps = sum(v for _, v in red["breakdown"]["idle_gaps"])
    assert 0 < gaps <= idle + 1e-9


def test_kernel_time_by_name(excerpt):
    devices, host = excerpt
    (_, lines), = devices.items()
    ops = lines[trace.OPS_LINE]
    red = trace.reduce_planes(devices, host, n_devices=1)
    want = sum(d for n, _, d in ops
               if n.startswith("%_paged_decode_attention")) * 1e-9
    assert want > 0
    got = trace.time_matching(red["op_time"], *readers.PAGED_KERNEL)
    assert got == pytest.approx(want, rel=1e-12)
    own = dict(red["breakdown"]["device_ops"])
    kernel = [k for k in own if k.startswith("_paged_decode_attention")]
    # a leaf op: its self time is its whole time
    if kernel:
        assert sum(own[k] for k in kernel) == pytest.approx(want, rel=1e-9)


def test_self_time_takes_nested_ops_out():
    ev = [("%while.1 = (s32[]) while(x)", 0, 100),
          ("%fusion.2 = f32[4] fusion(y)", 10, 30),
          ("%_paged_decode_attention.3 = bf16[2] custom-call(z)", 50, 20),
          ("%copy.4 = f32[4] copy(w)", 200, 5)]
    own = trace.self_times(ev)
    assert own["while (s32[])"] == pytest.approx(50e-9)
    assert own["fusion f32[4]"] == pytest.approx(30e-9)
    assert own["_paged_decode_attention bf16[2]"] == pytest.approx(20e-9)
    assert sum(own.values()) * 1e9 == pytest.approx(
        _busy_by_sweep(ev))


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]


def test_peaks_table_is_keyed_by_device_kind():
    bench = os.path.dirname(HERE)
    peak = trace.peak_for("TPU v5 lite", bench)
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in peak["source"]
    with pytest.raises(KeyError):
        trace.peak_for("cpu", bench)


def test_read_planes_of_a_cpu_trace(tmp_path):
    """A trace recorded here has host planes and no TPU plane: the reader
    returns no device and the reduction reports no busy time."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        f(x).block_until_ready()
    devices, host = trace.read_planes(trace.find_xplane(str(tmp_path)))
    assert devices == {} and host
    red = trace.reduce_planes(devices, host, n_devices=1)
    assert red["busy_s"] == 0 and red["devices"] == 0
    assert np.isfinite(red["busy_s"])
