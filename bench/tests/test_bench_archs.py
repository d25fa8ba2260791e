"""Architectures found by name (``bench/archs/``), on the CPU at tiny
sizes.

- The dense module reads what the benchmark read before architectures
  were modules: the weights, the gaps of the comparison and of the
  control, the kernels' work counts and ``payload_mfu``, on fixed inputs
  drawn from a seed, equal the values ``_readings`` gave when run on the
  benchmark of commit 9403de9 (``reference.make_weights``, the dense
  ``check.compare``, ``flops`` through the harness's instruments), on
  the same machine type.
- A new architecture is new files only (a module, a configuration that
  names it, a cell, a reader), and a whole run takes its weights,
  reference and work counts from it.
- An architecture whose reference differs from the program turns
  ``correct`` false: the comparison goes through the configuration's
  architecture.
"""

import hashlib
import json
import os
import types

import numpy as np
import pytest

from bench import check, harness, readers
from bench.record import Recorder

SEED = 20251018
PEAK = {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}

# Recorded with ``_readings`` at commit 9403de9, where the instruments
# took the model's sizes (``Run._sizes_of``) in place of its role.
PARENT = {
    "weights_sha256":
        "83ed16f08fcad82a0e947500e8a559a9c6b046f2234e576456cb605e6de23dda",
    "program": {"gen_ll_gap": 43.04059202010572,
                "score_gap": 0.7162416613827686},
    "control": {"gen_ll_gap": 0.523553729057312,
                "score_gap": 0.03915761311848958},
    "traced": {"fold": [1, 49422336.0, 416816.0, 5.089328449328449e-07],
               "paged_decode": [2, 54272.0, 29696.0,
                                3.625885225885226e-08]},
    "payload_mfu": 2.0013644670050762e-06,
}


def fake_recorder(prefix: int, seed: int) -> Recorder:
    """Candidates and score rows as the protocols would record them, with
    answers drawn from ``seed`` in place of the program's."""
    rng = np.random.default_rng(seed)
    rec = Recorder(prefix)
    for i, lens in enumerate([(7, 12), (16, 5, 9), (11,)]):
        rec.gen.append({
            "t": 1.0 + i, "uid": i, "ns": "default",
            "backbone": rng.normal(size=(prefix, 16)).astype(np.float32),
            "tokens": [rng.integers(1, 21, n).astype(np.int32)
                       for n in lens],
            "ll": rng.normal(-2.5, 0.5, len(lens)) * np.asarray(lens)})
    for i, n in enumerate([22, 30, 18, 30, 25]):
        rec.scores.append({
            "t": 2.0 + i, "uid": 10 + i, "ns": "default",
            "seq": rng.integers(1, 21, n).astype(np.int32),
            "target": rng.normal(size=16).astype(np.float32),
            "split": n - 10,
            "metrics": (float(rng.uniform(40, 90)), float(rng.uniform()),
                        float(rng.uniform(5, 25)))})
    return rec


def weights_sha256(weights) -> str:
    import jax
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(weights):
        a = np.asarray(leaf)
        h.update(str((a.shape, a.dtype.str)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _readings(r, gen_role, fold_role) -> dict:
    """The numbers of a built run ``r`` on fixed inputs: the weights'
    digest, the comparison's and the control's gaps on a fake record,
    what the instruments count for two decode steps and one scorer call,
    and ``payload_mfu`` over a 10 s window."""
    r.recorder = fake_recorder(r.recorder.prefix_len, SEED)
    words = np.random.SeedSequence([r.seed, 4]).generate_state(
        2, dtype=np.uint32)
    out = {"weights_sha256": weights_sha256(r.weights),
           "program": r.compare(),
           "control": check.compare(r.recorder, r.weights, r.roles(),
                                    r.config["check"], words,
                                    control=True)}
    r.peak, r.tracing = PEAK, True
    eng = types.SimpleNamespace(step=lambda params, temperature: None)
    step = r._count_step(eng, gen_role)
    for lens in ([0, 8, 16, 32], [5, 0, 0, 40]):
        eng.true_lens = np.asarray(lens, np.int32)
        step(None, 1.0)
    r._count_call(lambda *a: None, fold_role, 4, 64)()
    out["traced"] = {k: list(v) for k, v in sorted(r.traced.items())}
    r.t0, r.t1, r.extra = 0.0, 10.0, {}
    out["payload_mfu"] = readers.payload_mfu({"run": r, "peak": PEAK})
    return out


def test_dense_reads_as_before(tree, no_compile_cache):
    r = harness.Run("imrp-tiny", SEED, 3.0, False, root=tree,
                    bench_dir=tree + "/bench", require_tpu=False)
    r.build()
    roles = r.roles()
    got = _readings(r, roles["generator", "default"],
                    roles["scorer", "default"])
    assert got == PARENT


@pytest.mark.parametrize("generator", ["progen-s", "foldscore-s"])
def test_payload_serves_the_given_weights(tree, no_compile_cache,
                                          monkeypatch, generator):
    """The configuration's default generator and scorer, whichever
    registered models they are, get the benchmark's weights, and no init
    of the program runs for them."""
    from repro.configs.registry import get_reduced
    from repro.models import protein as prot
    r = harness.Run("imrp-tiny", SEED, 3.0, False, root=tree,
                    bench_dir=tree + "/bench", require_tpu=False)
    gen = r.config["models"]["generator"]
    cfg = get_reduced(generator)
    gen["registry"] = generator
    gen["sizes"] = {k: getattr(cfg, k) for k in gen["sizes"]}
    r.build()

    def no_init(key, cfg):
        raise AssertionError("the program initialised a model")

    monkeypatch.setattr(prot, "init_progen", no_init)
    monkeypatch.setattr(prot, "init_foldscore", no_init)
    p = harness.build_payload(r.config, r.cfgs, r.weights, 40)
    assert p.gen_cfg == cfg and p.gen_params is r.weights["generator"]
    assert p.fold_params is r.weights["scorer"]
    assert p.fold_sets["default"][1] is r.weights["scorer"]
    assert prot.init_progen is no_init and prot.init_foldscore is no_init


def test_payload_without_default_models(tree, no_compile_cache):
    """A configuration of param-set namespaces only: each namespace serves
    the benchmark's weights, and the payload's default models are the
    program's own, as before."""
    from repro.configs.registry import get_reduced
    r = harness.Run("gateway-tiny", SEED, 3.0, False, root=tree,
                    bench_dir=tree + "/bench", require_tpu=False)
    r.build()
    p = r.payload
    assert p.gen_stores["binder"].current()[1] is r.weights["generator"]
    assert p.fold_sets["multimer"][1] is r.weights["scorer"]
    assert p.gen_cfg == get_reduced("progen-s")
    assert p.fold_cfg == get_reduced("foldscore-s")
    assert p.gen_params is not r.weights["generator"]


# -- a new architecture, as new files only ---------------------------------

PROBE = '''"""The dense decoder under another name, noting each function the
benchmark calls and counting its decode kernel under a name of its own."""
from bench.archs import dense

CALLS = set()


def _noting(name, fn):
    def call(*a, **kw):
        CALLS.add(name)
        return fn(*a, **kw)
    return call


params = _noting("params", dense.params)
token_logprobs = _noting("token_logprobs", dense.token_logprobs)
fold_metrics = _noting("fold_metrics", dense.fold_metrics)
generator_flops = _noting("generator_flops", dense.generator_flops)
scorer_flops = _noting("scorer_flops", dense.scorer_flops)
scorer_call = _noting("scorer_call", dense.scorer_call)


def step_counts(m, kv_lens):
    CALLS.add("step_counts")
    return {"probe_decode": dense.step_counts(m, kv_lens)["paged_decode"]}
'''

SHIFTED = '''"""The dense decoder with a reference that is off: {what}."""
import jax.numpy as jnp

from bench.archs import dense
from bench.archs.dense import (params, generator_flops, scorer_flops,
                               scorer_call, step_counts)


def token_logprobs(params, backbones, tokens, *, m, quant=None):
    return dense.token_logprobs(params, backbones, tokens, m=m,
                                quant=quant) + {gen}


def fold_metrics(params, seqs, targets, seq_lens, splits, *, m,
                 quant=None):
    return dense.fold_metrics(params, seqs, targets, seq_lens, splits, m=m,
                              quant=quant) + {fold}
'''


def _snapshot(root):
    out = {}
    for d, _, fs in os.walk(root):
        for f in fs:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[p] = fh.read()
    return out


def _add_cell(tree, arch, cell, per_layer=()):
    """New files for architecture ``arch``: a configuration naming it for
    both models, a cell on the tiny traffic, and entries in
    BENCHMARK.json."""
    bench = os.path.join(tree, "bench")
    with open(os.path.join(bench, "configs", "imrp-progen-s-tiny.json")) as f:
        cfg = json.load(f)
    cfg["name"] = f"imrp-{arch}"
    for mdl in cfg["models"].values():
        mdl["arch"] = arch
    with open(os.path.join(bench, "configs", f"imrp-{arch}.json"), "w") as f:
        json.dump(cfg, f)
    bm_path = os.path.join(tree, "BENCHMARK.json")
    with open(bm_path) as f:
        bm = json.load(f)
    bm["configs"].append(dict(bm["configs"][0], name=f"imrp-{arch}",
                              file=f"bench/configs/imrp-{arch}.json"))
    bm["workloads"].append({"name": cell, "config": f"imrp-{arch}",
                            "traffic": "paged-tiny", "chips": 1,
                            "why": f"the {arch} architecture"})
    for m in per_layer:
        bm["per_layer"].append(dict(m, workloads=[cell]))
    with open(bm_path, "w") as f:
        json.dump(bm, f)


def test_new_architecture_is_new_files(tree, no_compile_cache, monkeypatch):
    """A module, a configuration naming it, a cell and a reader are added;
    no file already there changes, and the whole traced run takes the
    weights, the reference and the counts from the new module."""
    bench = os.path.join(tree, "bench")
    before = _snapshot(bench)
    before[os.path.join(tree, "BENCHMARK.json")] = None
    with open(os.path.join(bench, "archs", "probe.py"), "w") as f:
        f.write(PROBE)
    with open(os.path.join(bench, "metrics", "probe_steps.py"), "w") as f:
        f.write("def read(ctx):\n"
                "    n = ctx['run'].traced.get('probe_decode')\n"
                "    return float(n[0]) if n else None\n")
    mfu = {"name": "payload_mfu", "unit": "%", "better": "higher",
           "source": "program_counter", "layer": "payload models",
           "moves": "designs_per_s"}
    steps = dict(mfu, name="probe_steps", unit="steps", layer="kernels")
    _add_cell(tree, "probe", "imrp-probe", [mfu, steps])
    # The CPU has no entry in the table of peaks; a stand-in lets the
    # traced run count time and operations as on the chip.
    monkeypatch.setattr(harness.tracemod, "peak_for", lambda *a: PEAK)
    res = harness.run("imrp-probe", 2 ** 33 + 17, 3.0, 1, root=tree,
                      bench_dir=bench, require_tpu=False)
    assert res["correct"] is True, res["checks"]
    dev = res["device"]
    assert 0 < dev["window_s"] and 0 <= dev["busy_s"] <= dev["window_s"]
    assert res["metrics"]["probe_steps"]["value"] > 0
    assert res["metrics"]["payload_mfu"]["value"] > 0
    from bench import archs
    probe = archs.load("probe", bench)
    assert probe.CALLS == {"params", "token_logprobs", "fold_metrics",
                           "generator_flops", "scorer_flops", "scorer_call",
                           "step_counts"}
    for p, data in before.items():
        if data is not None:
            with open(p, "rb") as f:
                assert f.read() == data, p


@pytest.mark.parametrize("what,gen,fold,number", [
    ("one nat more for every token", "1.0", "0.0", "gen_ll_gap"),
    ("a pTM 0.1 higher", "0.0", "jnp.asarray([0.0, 0.1, 0.0])",
     "score_gap"),
])
def test_reference_of_the_configured_architecture_decides(
        tree, no_compile_cache, what, gen, fold, number):
    src = SHIFTED.format(what=what, gen=gen, fold=fold)
    with open(os.path.join(tree, "bench", "archs", "shifted.py"), "w") as f:
        f.write(src)
    _add_cell(tree, "shifted", "imrp-shifted")
    res = harness.run("imrp-shifted", 777000111, 3.0, 0, root=tree,
                      bench_dir=tree + "/bench", require_tpu=False)
    assert res["correct"] is False
    for name, c in res["checks"].items():
        assert (c["value"] > c["limit"]) is (name == number), res["checks"]
