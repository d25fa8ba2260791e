"""The trace reduction on a small excerpt of a trace recorded on a TPU v5e
(``data/trace_excerpt.json.gz``: 3,000 consecutive operation events of
device 0 in a traced run of ``imrp-paged-mixlen``, the executables and
host events around them), against counts made here a second way."""

import gzip
import json
import os
import types

import numpy as np
import pytest

from bench import harness, readers, trace

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
    devices, host, window = trace.read_planes(
        trace.find_xplane(str(tmp_path)))
    assert devices == {} and host and window is None
    red = trace.reduce_planes(devices, host, n_devices=1)
    assert red["busy_s"] == 0 and red["devices"] == 0
    assert np.isfinite(red["busy_s"]) and red["window_s"] is None


def _cpu_trace(path, annotate):
    """A CPU trace of a few jitted calls, inside a ``bench.slice`` span
    where ``annotate``; the span's length on the host's clock."""
    import time

    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(path))
    a = time.monotonic_ns()
    if annotate:
        with jax.profiler.TraceAnnotation(trace.SLICE):
            f(x).block_until_ready()
            time.sleep(0.02)
    b = time.monotonic_ns()
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    return b - a


def test_read_planes_finds_the_slice_span(tmp_path):
    """The harness's slice span lies on the host plane: the reader returns
    its interval, takes it out of the host events, and the reduction's
    window is its length."""
    host_ns = _cpu_trace(tmp_path, annotate=True)
    devices, host, window = trace.read_planes(
        trace.find_xplane(str(tmp_path)))
    assert window is not None
    lo, hi = window
    assert 20e6 <= hi - lo <= host_ns
    assert all(name != trace.SLICE for name, _, _ in host)
    assert any(lo <= s and s + d <= hi for _, s, d in host)
    red = trace.reduce_planes(devices, host, 1, window)
    assert red["window_s"] == (hi - lo) * 1e-9
    assert red["busy_s"] == 0 and red["devices"] == 0


@pytest.mark.parametrize("annotate,platform,error", [
    (True, "tpu", "0 /device:TPU:<n> planes"),
    (False, "tpu", "no bench.slice span"),
    (False, "cpu", None),
])
def test_traced_run_on_a_tpu_needs_the_slice_and_a_device(
        tmp_path, annotate, platform, error):
    """A trace read as a TPU run's (the platform's name faked) that has no
    device plane, or no slice span, raises instead of giving a result;
    off the chip a trace without the span keeps the host's slice."""
    _cpu_trace(tmp_path, annotate)
    run = types.SimpleNamespace(
        trace_dir=str(tmp_path), chips=1, slice_s=0.5,
        devices=[types.SimpleNamespace(platform=platform)])
    if error is None:
        assert harness.Run.reduce_trace(run)["window_s"] == 0.5
    else:
        with pytest.raises(harness.TraceError, match=error):
            harness.Run.reduce_trace(run)


# A device saturated across the slice [1000, 2000) ns: device 0 runs an op
# over the start, ops back to back, and a ``while`` over the end whose body
# has an op inside, one across the end and one after it, with ops wholly
# before and after the slice; device 1 starts late and idles twice.
SLICE = (1000, 2000)
SATURATED = {
    "/device:TPU:0": {
        trace.OPS_LINE: [
            ("%copy.1 = f32[4] copy(w)", 100, 700),
            ("%fusion.2 = f32[4] fusion(x)", 900, 200),
            ("%_paged_decode_attention.3 = bf16[2] custom-call(z)", 1100, 400),
            ("%while.4 = (s32[]) while(x)", 1500, 800),
            ("%fusion.5 = f32[4] fusion(y)", 1600, 100),
            ("%_paged_decode_attention.6 = bf16[2] custom-call(z)", 1950, 150),
            ("%copy.7 = f32[4] copy(w)", 2150, 100),
            ("%copy.8 = f32[4] copy(w)", 2400, 100)],
        trace.MODULES_LINE: [("jit_step(1)", 800, 800),
                             ("jit_step(1)", 1600, 700),
                             ("jit_other(2)", 2300, 100)]},
    "/device:TPU:1": {
        trace.OPS_LINE: [
            ("%fusion.9 = f32[4] fusion(x)", 1010, 290),
            ("%_paged_decode_attention.10 = bf16[2] custom-call(z)", 1350,
             640),
            ("%fusion.11 = f32[4] fusion(x)", 1995, 55)]},
}
SATURATED_HOST = [("loop", 0, 3000), ("admit", 1250, 200),
                  ("retire", 1980, 20)]


def test_saturated_device_is_clipped_to_the_slice():
    red = trace.reduce_planes(SATURATED, SATURATED_HOST, 2, SLICE)
    assert red["devices"] == 2
    assert red["window_s"] == pytest.approx(1000e-9, rel=1e-12)
    # device 0 busy all 1000 ns, device 1 for 290 + 640 + 5
    assert red["busy_s"] == pytest.approx((1000 + 935) / 2 * 1e-9,
                                          rel=1e-12)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert 0 <= readers.idle_share({"trace": red}) == pytest.approx(3.25)
    ops = red["op_time"]
    want_ops = {"copy.1": 0, "fusion.2": 100, "_paged_decode_attention.3": 400,
                "while.4": 500, "fusion.5": 100,
                "_paged_decode_attention.6": 50, "copy.7": 0, "copy.8": 0,
                "fusion.9": 290, "_paged_decode_attention.10": 640,
                "fusion.11": 5}
    assert ops == pytest.approx({k: v * 1e-9 for k, v in want_ops.items()})
    assert trace.time_matching(ops, *readers.PAGED_KERNEL) == pytest.approx(
        1090e-9)
    # the while's own time inside: 500 less its body's 100 and 50
    own = dict(red["breakdown"]["device_ops"])
    assert own == pytest.approx({"while (s32[])": 350e-9,
                                 "fusion f32[4]": 495e-9,
                                 "_paged_decode_attention bf16[2]": 1090e-9})
    assert sum(own.values()) * 1e9 == pytest.approx(1000 + 935)
    assert red["module_time"] == pytest.approx(
        {"jit_step(1)": 1000e-9, "jit_other(2)": 0.0})
    # idle: device 1's 10 ns from the slice's start, 50 and 5 between ops
    assert dict(red["breakdown"]["idle_gaps"]) == pytest.approx(
        {"loop": 10e-9, "admit": 50e-9, "retire": 5e-9})
    # unclipped, the same planes read busy longer than the slice
    whole = trace.reduce_planes(SATURATED, SATURATED_HOST, 2)
    assert whole["busy_s"] > red["window_s"]


def _clipped(events, lo, hi):
    return [(n, max(s, lo), min(s + d, hi) - max(s, lo))
            for n, s, d in events if min(s + d, hi) > max(s, lo)]


def test_excerpt_cut_by_a_slice(excerpt):
    """A slice whose edges cut through ``while`` loops of the recorded
    trace: busy time by the sweep over the clipped intervals, kernel,
    self and module time clipped, idle gaps inside the slice."""
    devices, host = excerpt
    (_, lines), = devices.items()
    ops = lines[trace.OPS_LINE]
    loops = sorted((s, d) for n, s, d in ops
                   if n.startswith("%while"))
    lo = loops[1][0] + loops[1][1] // 2
    hi = loops[-2][0] + loops[-2][1] // 3
    red = trace.reduce_planes(devices, host, 1, (lo, hi))
    cut = _clipped(ops, lo, hi)
    assert len(cut) < len(ops)
    busy = _busy_by_sweep(cut)
    assert red["busy_s"] == pytest.approx(busy * 1e-9, abs=1e-9)
    assert 0 < red["busy_s"] < red["window_s"] == (hi - lo) * 1e-9
    assert sum(trace.self_times(ops, (lo, hi)).values()) * 1e9 == \
        pytest.approx(busy)
    want = sum(d for n, _, d in cut
               if n.startswith("%_paged_decode_attention")) * 1e-9
    assert 0 < want < trace.time_matching(
        trace.reduce_planes(devices, host, 1)["op_time"],
        *readers.PAGED_KERNEL)
    assert trace.time_matching(red["op_time"], *readers.PAGED_KERNEL) == \
        pytest.approx(want, rel=1e-12)
    mods = _clipped(lines[trace.MODULES_LINE], lo, hi)
    assert sum(red["module_time"].values()) == pytest.approx(
        sum(d for _, _, d in mods) * 1e-9, rel=1e-12)
    gaps = sum(v for _, v in red["breakdown"]["idle_gaps"])
    assert 0 < gaps <= red["window_s"] - red["busy_s"] + 1e-12
