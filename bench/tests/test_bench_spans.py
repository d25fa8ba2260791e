"""The readers of the program's step counts, coordinator span and GC
pauses: hand-made runs, a run of a program without them, and a traced
run of the tiny session cell on the CPU that loads every reader."""

import json
import os
import types

import pytest

from bench import harness
from bench.tests.conftest import HERE
from repro.obs import MetricsRegistry

ROOT = os.path.dirname(os.path.dirname(HERE))
NEW = ("decode_slot_fill", "coordinator_busy_share", "gc_pause_share")


def _reader(name):
    return harness.load_module(
        os.path.join(ROOT, "bench", "metrics", f"{name}.py"),
        "bench_metric_" + name).read


def _run(gen=(), registries=(), t0=10.0, t1=14.0):
    return types.SimpleNamespace(dispatches={"gen": list(gen)},
                                 registries=list(registries), t0=t0, t1=t1)


def test_decode_slot_fill_arithmetic():
    gen = [{"decode": "paged", "bucket": 32, "steps": 10, "slot_steps": 160},
           {"decode": "paged", "bucket": 8, "steps": 5, "slot_steps": 40},
           {"decode": "dense", "bucket": 4, "rows": 4}]
    got = _reader("decode_slot_fill")({"run": _run(gen)})
    assert got == pytest.approx(100.0 * (160 + 40) / (32 * 10 + 8 * 5))


def test_span_shares_sum_over_registries():
    a, b = MetricsRegistry(), MetricsRegistry()
    for reg, vals in ((a, (0.1, 0.2)), (b, (0.3,))):
        for v in vals:
            reg.histogram("coordinator.handle_s").observe(v)
    a.histogram("gc.pause_s", generation=0).observe(0.01)
    a.histogram("gc.pause_s", generation=2).observe(0.25)
    b.histogram("gc.pause_s", generation=1).observe(0.04)
    ctx = {"run": _run(registries=(a, b))}
    assert _reader("coordinator_busy_share")(ctx) == pytest.approx(15.0)
    assert _reader("gc_pause_share")(ctx) == pytest.approx(7.5)


def test_readers_find_nothing_in_a_program_without_them():
    """A program whose log and registries lack the counts (the code before
    them) gives no value, and raises nothing."""
    reg = MetricsRegistry()
    reg.histogram("task.queue_wait_s", kind="generate_batch").observe(1.0)
    ctx = {"run": _run([{"decode": "paged", "bucket": 32, "rows": 3}],
                       (reg,))}
    assert [_reader(n)(ctx) for n in NEW] == [None, None, None]


def test_traced_run_reports_the_new_metrics(tree, no_compile_cache):
    bm_path = os.path.join(tree, "BENCHMARK.json")
    with open(bm_path) as f:
        bm = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        src = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW:
        bm["per_layer"].append(dict(src[name], workloads=["imrp-tiny"]))
    with open(bm_path, "w") as f:
        json.dump(bm, f)
    res = harness.run("imrp-tiny", 4294967311, 3.0, 1, root=tree,
                      bench_dir=tree + "/bench", require_tpu=False)
    m = res["metrics"]
    assert res["correct"] is True, res["checks"]
    assert 0 < m["decode_slot_fill"]["value"] <= 100
    assert 0 < m["coordinator_busy_share"]["value"] < 100
    assert 0 < m["gc_pause_share"]["value"] < 100
    assert all(m[n]["unit"] == "%" for n in NEW)
