"""The reader of the paged decode step's host time: hand-made dispatch
records, records of a program without the field, and a traced run of the
tiny session cell on the CPU that reports it."""

import json
import os
import types

import pytest

from bench import harness
from bench.tests.conftest import HERE

ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = "paged_step_host_ms"


def _read(gen):
    read = harness.load_module(
        os.path.join(ROOT, "bench", "metrics", f"{NAME}.py"),
        "bench_metric_" + NAME).read
    return read({"run": types.SimpleNamespace(dispatches={"gen": gen})})


def test_step_time_over_steps():
    gen = [{"decode": "paged", "bucket": 32, "steps": 10, "slot_steps": 160,
            "step_host_s": 0.02},
           {"decode": "paged", "bucket": 8, "steps": 30, "slot_steps": 40,
            "step_host_s": 0.04},
           {"decode": "dense", "bucket": 4, "rows": 4}]
    assert _read(gen) == pytest.approx(1000.0 * 0.06 / 40)


@pytest.mark.parametrize("gen", [
    [],
    [{"decode": "dense", "bucket": 4, "rows": 4}],
    [{"decode": "paged", "bucket": 32, "steps": 10, "slot_steps": 160}],
])
def test_nothing_to_read(gen):
    """No paged dispatch, or a program whose records lack the step time
    (the code before it): no value, and nothing raised."""
    assert _read(gen) is None


def test_traced_run_reports_step_time(tree, no_compile_cache):
    bm_path = os.path.join(tree, "BENCHMARK.json")
    with open(bm_path) as f:
        bm = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        src = {m["name"]: m for m in json.load(f)["per_layer"]}
    bm["per_layer"].append(dict(src[NAME], workloads=["imrp-tiny"]))
    with open(bm_path, "w") as f:
        json.dump(bm, f)
    res = harness.run("imrp-tiny", 4294967311, 3.0, 1, root=tree,
                      bench_dir=tree + "/bench", require_tpu=False)
    assert res["correct"] is True, res["checks"]
    dev = res["device"]
    assert 0 < dev["window_s"] and 0 <= dev["busy_s"] <= dev["window_s"]
    m = res["metrics"][NAME]
    assert m["unit"] == "ms" and m["value"] > 0
