"""Observability layer tests: metrics sketch accuracy, span-chain
lifecycle tracing through the live executor, Perfetto export validation,
fake-clock deterministic timing, and the golden report-section schemas
that must survive the registry rebuild."""

import gc
import glob
import json
import threading
import time

import numpy as np
import pytest

from repro.core.pipeline import ResourceRequest, Task, TaskState
from repro.obs import (CompileWatcher, MetricsRegistry, Telemetry, Tracer,
                       span, validate_trace, write_metrics, write_trace)
from repro.obs.metrics import Histogram
from repro.runtime import AsyncExecutor, DeviceAllocator
from repro.runtime.executor import CoalesceRule


class FakeDev:
    _n = 0

    def __init__(self):
        FakeDev._n += 1
        self.id = FakeDev._n


def fake_grid(*shape):
    n = int(np.prod(shape))
    return np.array([FakeDev() for _ in range(n)], dtype=object).reshape(shape)


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += float(dt)


def wait_for(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.005)
    return False


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_histogram_quantiles_match_numpy():
    """The streaming sketch's p50/p95 stay within the log-bucket relative
    resolution of exact percentiles on a heavy-tailed sample."""
    rng = np.random.default_rng(0)
    sample = rng.lognormal(mean=-2.0, sigma=1.5, size=20_000)
    h = Histogram()
    for v in sample:
        h.observe(float(v))
    for q in (0.5, 0.95):
        exact = float(np.quantile(sample, q))
        approx = h.quantile(q)
        assert abs(approx - exact) / exact < 0.10
    s = h.summary()
    assert s["count"] == len(sample)
    assert s["max"] == pytest.approx(float(sample.max()))
    assert s["mean"] == pytest.approx(float(sample.mean()), rel=1e-6)


def test_registry_series_labels_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("tasks.completed", kind="predict").inc(3)
    reg.counter("tasks.completed", kind="generate").inc()
    reg.gauge("queue.depth", band=0).set(7)
    reg.histogram("task.device_s", kind="predict").observe(0.5)
    snap = reg.snapshot()
    assert snap["tasks.completed{kind=predict}"] == 3
    assert snap["tasks.completed{kind=generate}"] == 1
    assert snap["queue.depth{band=0}"] == 7
    assert snap["task.device_s{kind=predict}"]["count"] == 1
    by_kind = reg.labeled("tasks.completed", "kind")
    assert {k: c.get() for k, c in by_kind.items()} == {
        "predict": 3, "generate": 1}
    assert reg.value("tasks.completed", kind="predict") == 3
    assert reg.value("nope", default=-1) == -1
    with pytest.raises(TypeError):
        reg.gauge("tasks.completed", kind="predict")


# ---------------------------------------------------------------------------
# program spans on the profiler's clock, GC pauses
# ---------------------------------------------------------------------------

def _profiled_spans(trace_dir):
    """``impress.*`` events of the host planes of the profile under
    ``trace_dir``: [(name, start_ns, end_ns, stats)]."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("impress."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def test_span_lands_on_profiler_host_plane_nested(tmp_path):
    import jax
    with jax.profiler.trace(str(tmp_path)):
        with span("outer", kind="generate_batch"):
            with span("inner"):
                time.sleep(0.002)
    got = {name: (s, e, stats)
           for name, s, e, stats in _profiled_spans(tmp_path)}
    assert set(got) == {"impress.outer", "impress.inner"}
    (os_, oe, ostats), (is_, ie, _) = got["impress.outer"], \
        got["impress.inner"]
    assert os_ <= is_ < ie <= oe            # nested as entered
    assert ie - is_ >= 2e6
    assert ostats["kind"] == "generate_batch"


def test_executor_dispatch_span_carries_kind(tmp_path):
    import jax
    ex, _ = traced_executor()
    ex.register("noop", lambda sm, p: p["x"])
    with jax.profiler.trace(str(tmp_path)):
        ex.submit(Task(kind="noop", payload={"x": 1}))
        done = ex.drain(timeout=10)
    ex.shutdown()
    assert done is not None and done.state == TaskState.DONE
    disp = [(s, e, st) for name, s, e, st in _profiled_spans(tmp_path)
            if name == "impress.executor.dispatch"]
    assert len(disp) == 1 and disp[0][2]["kind"] == "noop"


def test_gc_pause_recorded_while_watching():
    reg = MetricsRegistry()
    with CompileWatcher(reg):
        gc.collect()
    full = reg.histogram("gc.pause_s", generation=2)
    assert full.count >= 1 and full.sum > 0
    n = full.count
    gc.collect()                            # no watcher of this registry
    assert full.count == n


def test_gc_pause_recorded_as_span(tmp_path):
    import jax
    with CompileWatcher(MetricsRegistry()):
        with jax.profiler.trace(str(tmp_path)):
            gc.collect()
    pauses = [st for name, _, _, st in _profiled_spans(tmp_path)
              if name == "impress.gc"]
    assert any(st.get("generation") == 2 for st in pauses)


def test_gc_hook_takes_no_metrics_lock():
    """A collection can start while this thread holds a metrics lock: the
    hook queues the pause instead of taking the lock, so nothing
    deadlocks, and the pause lands in the registry on exit."""
    reg = MetricsRegistry()
    done = threading.Event()

    def body():
        with CompileWatcher(reg):
            h = reg.histogram("gc.pause_s", generation=2)
            with reg._lock, h._lock:
                gc.collect()
        done.set()

    th = threading.Thread(target=body, daemon=True)
    th.start()
    th.join(10)
    assert done.is_set()
    assert reg.histogram("gc.pause_s", generation=2).count >= 1


# ---------------------------------------------------------------------------
# span tracing through the live executor
# ---------------------------------------------------------------------------

def traced_executor(n_devices=4, **kw):
    tel = Telemetry(tracer=Tracer())
    alloc = DeviceAllocator(fake_grid(n_devices), telemetry=tel)
    ex = AsyncExecutor(alloc, max_workers=2, telemetry=tel, **kw)
    return ex, tel


def test_task_lifecycle_chain_and_grant_spans():
    ex, tel = traced_executor()
    ex.register("noop", lambda sm, p: p["x"])
    ex.submit(Task(kind="noop", payload={"x": 1}))
    done = ex.drain(timeout=10)
    assert done is not None and done.state == TaskState.DONE
    events = [e for e, _ in done.trace["events"]]
    assert events[:4] == ["submitted", "queued", "granted", "dispatched"]
    assert events[-1] == "completed"
    times = [t for _, t in done.trace["events"]]
    assert times == sorted(times)
    assert len(done.trace["dispatches"]) == 1
    # the dispatch span links back and the grant span covered real devices
    assert wait_for(lambda: tel.tracer.grant_records()
                    and tel.tracer.grant_records()[0]["end"] is not None)
    (disp,) = tel.tracer.dispatch_records()
    assert disp["members"] == [done.uid] and disp["status"] == "ok"
    (grant,) = tel.tracer.grant_records()
    assert grant["devices"] and grant["n_devices"] == len(grant["devices"])
    ex.shutdown()


def test_coalesced_members_link_to_fused_dispatch_span():
    """Every member of a fused batch records the same dispatch span id,
    and the span records every member uid (the flow-arrow invariant)."""
    ex, tel = traced_executor(n_devices=1)
    rule = CoalesceRule(
        key=lambda t: t.kind,
        merge=lambda ms: {"xs": [m.payload["x"] for m in ms]},
        split=lambda ms, r: list(r),
        rows=lambda t: 1, max_rows=16)
    ex.register("fuse", lambda sm, p: [x * 2 for x in p["xs"]])
    ex.register_coalescable("fuse", rule)
    gate = threading.Event()
    ex.register("blocker", lambda sm, p: gate.wait(timeout=30))
    ex.submit(Task(kind="blocker", payload={}))
    wait_for(lambda: ex.allocator.n_free == 0)
    tasks = [Task(kind="fuse", payload={"x": i}) for i in range(4)]
    for t in tasks:
        ex.submit(t)
    gate.set()
    done = [ex.drain(timeout=10) for _ in range(5)]
    assert all(d is not None for d in done)
    members = [d for d in done if d.kind == "fuse"]
    assert {d.result for d in members} == {0, 2, 4, 6}
    span_ids = {d.trace["dispatches"][0] for d in members}
    assert len(span_ids) == 1            # all rows in one fused dispatch
    span_id = span_ids.pop()
    span = next(s for s in tel.tracer.dispatch_records()
                if s["id"] == span_id)
    assert sorted(span["members"]) == sorted(d.uid for d in members)
    assert span["rows"] == 4
    ex.shutdown()


def test_retry_and_failure_marks(tmp_path):
    ex, tel = traced_executor()
    calls = {"n": 0}

    def flaky(sm, p):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("boom")
        return "ok"

    ex.register("flaky", flaky)
    ex.submit(Task(kind="flaky", payload={}))
    done = ex.drain(timeout=10)
    assert done.state == TaskState.DONE
    events = [e for e, _ in done.trace["events"]]
    assert "retried" in events
    assert events.count("queued") == 2   # requeue after the failed attempt
    assert len(done.trace["dispatches"]) == 2
    assert tel.metrics.value("tasks.retried", kind="flaky") == 1
    ex.shutdown()


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------

def test_trace_export_validates_and_has_full_chains(tmp_path):
    ex, tel = traced_executor()
    ex.register("noop", lambda sm, p: None)
    for _ in range(3):
        ex.submit(Task(kind="noop", payload={}))
    for _ in range(3):
        assert ex.drain(timeout=10) is not None
    wait_for(lambda: all(g["end"] is not None
                         for g in tel.tracer.grant_records()))
    ex.shutdown()
    path = write_trace(tel.tracer, str(tmp_path / "trace.json"))
    info = validate_trace(path)
    assert info["kinds"] == {"noop": 3}
    assert info["full_chains"] == 3
    mpath = write_metrics(tel.metrics, str(tmp_path / "metrics.json"))
    snap = json.load(open(mpath))
    assert snap["tasks.completed{kind=noop}"] == 3
    assert "devices.free" in snap


# ---------------------------------------------------------------------------
# fake-clock deterministic timing (injectable now_fn)
# ---------------------------------------------------------------------------

def test_fake_clock_exact_device_time():
    """With the executor on a fake clock, a payload that advances it by a
    known amount yields that exact task duration — no sleeps, no slack."""
    clock = FakeClock()
    alloc = DeviceAllocator(fake_grid(2))
    ex = AsyncExecutor(alloc, max_workers=1, now_fn=clock)

    def work(sm, p):
        clock.advance(0.25)
        return None

    ex.register("work", work)
    ex.submit(Task(kind="work", payload={}))
    done = ex.drain(timeout=10)
    assert done.duration() == 0.25
    assert ex.telemetry.metrics.histogram(
        "task.device_s", kind="work").summary()["max"] == 0.25
    ex.shutdown()


def test_fake_clock_exact_queue_wait():
    """A task held behind a blocker on a one-device grid records exactly
    the fake-clock time that passed while it waited."""
    clock = FakeClock()
    alloc = DeviceAllocator(fake_grid(1))
    ex = AsyncExecutor(alloc, max_workers=2, now_fn=clock)
    gate = threading.Event()
    ex.register("blocker", lambda sm, p: gate.wait(timeout=30))
    ex.register("noop", lambda sm, p: None)
    ex.submit(Task(kind="blocker", payload={}))
    wait_for(lambda: ex.allocator.n_free == 0)
    waiting = Task(kind="noop", payload={})
    ex.submit(waiting)
    clock.advance(2.0)
    gate.set()
    done = [ex.drain(timeout=10) for _ in range(2)]
    assert all(d is not None for d in done)
    got = next(d for d in done if d.kind == "noop")
    q, r = got.timestamps["QUEUED"], got.timestamps["RUNNING"]
    assert r - q == 2.0
    assert ex.telemetry.metrics.histogram(
        "task.queue_wait_s", kind="noop").summary()["max"] == 2.0
    ex.shutdown()


# ---------------------------------------------------------------------------
# golden report-section schemas (must survive the registry rebuild)
# ---------------------------------------------------------------------------

def test_golden_stat_schemas():
    """The pre-telemetry stat sections keep their exact key sets now that
    they are derived from the metrics registry."""
    ex, tel = traced_executor(n_devices=2)
    rule = CoalesceRule(
        key=lambda t: t.kind,
        merge=lambda ms: {"xs": [m.payload["x"] for m in ms]},
        split=lambda ms, r: list(r),
        rows=lambda t: 1, max_rows=8)
    ex.register("fuse", lambda sm, p: p["xs"])
    ex.register_coalescable("fuse", rule)
    for i in range(3):
        t = Task(kind="fuse", payload={"x": i}, stage="fold",
                 resources=ResourceRequest(n_devices=1, rows=1))
        ex.submit(t)
    for _ in range(3):
        assert ex.drain(timeout=10) is not None

    assert set(ex.coalesce_stats()) == {
        "dispatches", "fused_dispatches", "tasks_fused", "rows_dispatched",
        "mean_tasks_per_dispatch"}
    stages = ex.stage_stats()
    assert set(stages) == {"fold"}
    assert set(stages["fold"]) == {
        "dispatches", "tasks", "rows", "run_s", "wait_s",
        "mean_tasks_per_dispatch", "mean_wait_s"}
    assert stages["fold"]["tasks"] == 3
    assert set(ex.allocator.shape_stats()) == {
        "grants", "mean_granted", "mean_rows_per_device", "downsized"}
    sss = ex.allocator.stage_shape_stats()
    assert set(sss) == {"fold"}
    assert set(sss["fold"]) == {"grants", "devices", "rows", "mean_granted",
                                "mean_rows_per_device"}
    stats = ex.stats()
    assert set(stats) == {
        "coalesce", "n_tasks", "n_done", "n_failed", "n_retried",
        "n_preempted", "utilization", "mean_exec_setup_s", "mean_running_s"}
    assert stats["n_done"] == 3
    tel_sum = ex.telemetry_summary()
    assert set(tel_sum) == {"kinds", "counters", "spans"}
    assert set(tel_sum["kinds"]["fuse"]) == {"queue_wait_s", "device_s"}
    assert {"count", "mean", "p50", "p95", "max"} == set(
        tel_sum["kinds"]["fuse"]["device_s"])
    ex.shutdown()


def test_queue_depth_gauges_track_bands():
    clock = FakeClock()
    alloc = DeviceAllocator(fake_grid(1))
    ex = AsyncExecutor(alloc, max_workers=1, now_fn=clock)
    gate = threading.Event()
    ex.register("blocker", lambda sm, p: gate.wait(timeout=30))
    ex.register("noop", lambda sm, p: None)
    ex.submit(Task(kind="blocker", payload={}))
    wait_for(lambda: ex.allocator.n_free == 0)
    for band in (0, 0, 1):
        ex.submit(Task(kind="noop", payload={}, band=band))
    m = ex.telemetry.metrics
    assert m.value("queue.depth", band=0) == 2
    assert m.value("queue.depth", band=1) == 1
    gate.set()
    for _ in range(4):
        assert ex.drain(timeout=10) is not None
    assert wait_for(lambda: m.value("queue.depth", band=0) == 0
                    and m.value("queue.depth", band=1) == 0)
    ex.shutdown()


# ---------------------------------------------------------------------------
# session e2e: trace_dir opt-in + report["telemetry"] + golden report keys
# ---------------------------------------------------------------------------

def test_session_trace_dir_end_to_end(tmp_path):
    from repro.session import CampaignSpec, ImpressSession, ProtocolSpec
    spec = CampaignSpec(
        structures=1, receptor_len=12,
        protocols=(ProtocolSpec("im-rp", n_cycles=1, n_candidates=2,
                                score_batch=2),),
        timeout=120.0, trace_dir=str(tmp_path))
    with ImpressSession(spec) as sess:
        rep = sess.run()
        live = sess.metrics_snapshot()
    # pre-existing report sections survive the telemetry rebuild
    for key in ("stages", "utilization", "len_occupancy", "makespan_s",
                "allocator_shapes"):
        assert key in rep.raw
    assert set(rep["executor"]["coalesce"]) == {
        "dispatches", "fused_dispatches", "tasks_fused", "rows_dispatched",
        "mean_tasks_per_dispatch"}
    tel = rep["telemetry"]
    assert tel["kinds"], "per-kind queue-wait/device-time summaries missing"
    for kind, summ in tel["kinds"].items():
        assert {"p50", "p95"} <= set(summ["queue_wait_s"])
        assert {"p50", "p95"} <= set(summ["device_s"])
    info = validate_trace(tel["trace_path"])
    completed = sum(tel["counters"]["completed"].values())
    assert completed > 0
    assert info["full_chains"] >= completed
    assert "coalesce.dispatches" in live
