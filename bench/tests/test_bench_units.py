"""Units of the benchmark's yardstick, on the CPU."""

import json
import os

import pytest

from bench import flops, harness, mix, structures

from bench.tests.conftest import HERE


def test_flops_hand_counts():
    m = {"n_layers": 1, "d_model": 4, "n_heads": 2, "n_kv_heads": 1,
         "head_dim": 2, "d_ff": 8, "vocab_size": 32, "frontend_seq": 2}
    # q 16 + k,v 16 + o 16 + mlp 96 (+ 8 norm scales, not multiplied)
    assert flops.layer_params(m) == 152
    # 2 * 144 * 3 linear + 4 * 2 * 2 * (1 + 2 + 3) attention
    assert flops.stack_flops(m, 3) == 864 + 96
    # structure projection 2*16*4*2, stack over 2 + 1, head 2*4*32*1
    assert flops.generator_flops(m, 1) == 256 + 960 + 256
    g = {"n_layers": 6, "n_heads": 8, "n_kv_heads": 4, "head_dim": 32}
    f, b = flops.paged_decode_step(g, [0, 66, 70])
    assert f == 6 * 4 * 8 * 32 * 136
    # K and V of 136 entries at 4 heads x 32 x bf16, q and o of 2 rows
    assert b == 6 * (2 * 4 * 32 * 2 * 136 + 2 * 2 * 8 * 32 * 2)
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert flops.roofline_s(2e12, 1e9, peak) == 2.0
    assert flops.roofline_s(1e12, 3e9, peak) == 3.0


def test_scorer_call_counts_padding():
    m = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 2,
         "head_dim": 4, "d_ff": 16, "vocab_size": 32}
    f1, b1 = flops.scorer_call(m, 1, 16)
    f4, b4 = flops.scorer_call(m, 4, 16)
    assert f4 == 4 * f1 == 4 * flops.scorer_flops(m, 16)
    assert b4 - b1 == 3 * (4 * 16 + 4 * 16 + 3 * 4)


@pytest.mark.parametrize("spec", [
    dict(structures=16, receptor_len=[80, 96, 112, 128], peptide_len=10,
         seed=123),
    dict(structures=2, receptor_len=[96, 128], peptide_len=10,
         seed=2 ** 29 + 7),
    dict(structures=3, receptor_len=48, peptide_len=6, seed=0),
])
def test_structures_copy_matches_program(spec):
    from repro.data import protein_design_tasks
    structures.check_program(protein_design_tasks, spec)


def test_structures_check_catches_a_difference():
    from repro.data import protein_design_tasks

    def shifted(n, **kw):
        out = protein_design_tasks(n, **kw)
        out[-1]["backbone"] = out[-1]["backbone"] + 1e-3
        return out

    with pytest.raises(ValueError):
        structures.check_program(shifted, dict(
            structures=4, receptor_len=[80, 96], peptide_len=10, seed=5))


def test_arrivals_same_work_for_every_seed():
    tr = mix.load_traffic("binder-poisson")
    a, b = mix.arrivals(tr, 1, 30.0), mix.arrivals(tr, 2 ** 40 + 3, 30.0)
    assert len(a) == len(b) == round(tr["arrivals"]["rate_per_s"] * 30)
    assert [x["due_s"] for x in a] == sorted(x["due_s"] for x in a)
    for key in ("due_s", "tenant"):
        assert [x[key] for x in a] == [x[key] for x in b]
    assert [x["body"]["receptor_len"] for x in a] == \
        [x["body"]["receptor_len"] for x in b]
    assert [x["body"]["seed"] for x in a] != [x["body"]["seed"] for x in b]
    shares = tr["arrivals"]["tenant_shares"]
    for t, share in shares.items():
        got = sum(x["tenant"] == t for x in a) / len(a)
        assert abs(got - share / sum(shares.values())) <= 1 / len(a)
    assert all(0 <= x["due_s"] < 30.0 for x in a + b)
    assert mix.arrivals(tr, 1, 30.0) == a
    assert all(0 <= x["body"]["seed"] < 2 ** 30 for x in a)


def test_sub_seed_takes_any_seed():
    for s in (0, 1, 2 ** 31 + 5, 2 ** 63 + 11):
        v = mix.sub_seed(s, 1, 2)
        assert 0 <= v < 2 ** 30 and v == mix.sub_seed(s, 1, 2)
    assert mix.sub_seed(7, 1, 2) != mix.sub_seed(7, 1, 3)


def test_discovery_finds_new_files_without_edits(tree):
    """A new cell, configuration and per-layer metric are new files plus
    entries in BENCHMARK.json; no file already there changes."""
    bench = os.path.join(tree, "bench")
    before = {}
    for d, _, fs in os.walk(bench):
        for f in fs:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()
    with open(os.path.join(bench, "configs", "imrp-progen-s-tiny.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "imrp-other"
    with open(os.path.join(bench, "configs", "imrp-other.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "paged-tiny.json")) as f:
        tr = json.load(f)
    tr["campaign"]["spec"]["receptor_len"] = [16]
    with open(os.path.join(bench, "traffic", "paged-one-length.json"),
              "w") as f:
        json.dump(tr, f)
    with open(os.path.join(bench, "metrics", "designs_seen.py"), "w") as f:
        f.write("def read(ctx):\n    return 42.0\n")
    bm_path = os.path.join(tree, "BENCHMARK.json")
    with open(bm_path) as f:
        bm = json.load(f)
    bm["configs"].append(dict(bm["configs"][0], name="imrp-other",
                              file="bench/configs/imrp-other.json"))
    bm["workloads"].append({"name": "imrp-one-length", "config": "imrp-other",
                            "traffic": "paged-one-length", "chips": 1,
                            "why": "one receptor length"})
    bm["per_layer"].append({"name": "designs_seen", "unit": "designs",
                            "better": "higher", "source": "program_counter",
                            "layer": "coordinator", "moves": "designs_per_s",
                            "workloads": ["imrp-one-length"]})
    with open(bm_path, "w") as f:
        json.dump(bm, f)
    _, cell, centry, config, traffic = harness.find_cell(
        "imrp-one-length", tree, bench)
    assert config["name"] == "imrp-other"
    assert traffic["campaign"]["spec"]["receptor_len"] == [16]
    assert traffic["entry"] == "session_loop"
    names = [m["name"] for m in bm["per_layer"]
             if "imrp-one-length" in m.get("workloads", ())]
    assert names == ["designs_seen"]
    mod = harness.load_module(os.path.join(bench, "metrics",
                                           "designs_seen.py"), "m_new")
    assert mod.read({}) == 42.0
    for p, data in before.items():
        assert open(p, "rb").read() == data, p


def test_every_named_file_exists():
    """Each configuration, traffic, entry kind and per-layer metric that
    BENCHMARK.json names has its file."""
    root = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    for c in bm["configs"]:
        assert os.path.isfile(os.path.join(root, c["file"]))
    for w in bm["workloads"]:
        tr = mix.load_traffic(w["traffic"])
        assert os.path.isfile(os.path.join(root, "bench", "entries",
                                           tr["entry"] + ".py"))
    for m in bm["per_layer"]:
        assert os.path.isfile(os.path.join(root, "bench", "metrics",
                                           m["name"] + ".py"))
