"""Length-bucketed masked batching tests: bucket tables, masked-scoring
equivalence (a padded mixed-length batch scores bit-close to each row
alone at its true length), coalesce-rule key/merge/split round-trips over
heterogeneous lengths, batch-composition independence of masked sampling,
the mixed-length campaign end to end through the session facade, and
where the persistent compilation cache lives."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import ProteinPayload, Task
from repro.core.payload import (generate_batch_coalesce_rule,
                                predict_batch_coalesce_rule)
from repro.models import protein as prot
from repro import session as session_mod
from repro.runtime import DeviceAllocator
from repro.runtime.allocator import (LENGTH_BUCKETS, bucket_len,
                                     choose_length_buckets)
from repro.session import (DEFAULT_COMPILATION_CACHE, CampaignSpec,
                           ImpressSession, ProtocolSpec,
                           campaign_length_buckets, enable_compilation_cache)

ATOL = 1e-5
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- bucket tables -----------------------------------------------------------


def test_bucket_len_global_table():
    assert bucket_len(1) == LENGTH_BUCKETS[0]
    assert bucket_len(17) == 24
    assert bucket_len(64) == 64
    assert bucket_len(65) == 96
    # past the top edge: round up to a multiple of it, never unbounded
    top = LENGTH_BUCKETS[-1]
    assert bucket_len(top + 1) == 2 * top
    assert bucket_len(2 * top + 5) == 3 * top


def test_bucket_len_custom_edges():
    assert bucket_len(10, (12, 20)) == 12
    assert bucket_len(12, (12, 20)) == 12
    assert bucket_len(13, (12, 20)) == 20
    assert bucket_len(25, (12, 20)) == 40   # beyond top: multiple of 20


def test_choose_length_buckets_density():
    lengths = [49, 53, 57, 60, 64, 101, 103]
    edges = choose_length_buckets(lengths, max_pad=0.125)
    assert edges == tuple(sorted(edges))
    for L in lengths:
        b = bucket_len(L, edges)
        assert b in edges
        assert L <= b <= L / (1.0 - 0.125)   # per-row fill >= 1 - max_pad
    assert choose_length_buckets([]) is None
    assert choose_length_buckets([24, 24, 24]) == (24,)


def test_campaign_length_buckets_from_spec():
    # homogeneous campaign: no buckets -> exact seed paths
    assert campaign_length_buckets(CampaignSpec(receptor_len=24)) is None
    spec = CampaignSpec(receptor_len=(10, 12, 14), peptide_len=4)
    edges = campaign_length_buckets(spec)
    for L in (10, 12, 14, 14 + 4):
        assert bucket_len(L, edges) >= L
    # explicit override wins
    spec = CampaignSpec(receptor_len=(10, 12), length_buckets=(16, 32))
    assert campaign_length_buckets(spec) == (16, 32)


# -- masked model equivalence ------------------------------------------------


@pytest.fixture(scope="module")
def payload():
    return ProteinPayload(jax.random.PRNGKey(0), reduced=True, length=16)


@pytest.fixture(scope="module")
def submesh():
    alloc = DeviceAllocator(jax.devices())
    sub = alloc.request(1)
    assert sub is not None
    return sub


def mixed_rows(rng, lens, pad_to):
    seqs = np.zeros((len(lens), pad_to), np.int32)
    rows = []
    for i, L in enumerate(lens):
        row = rng.integers(1, 20, size=L).astype(np.int32)
        seqs[i, :L] = row
        rows.append(row)
    return seqs, rows


def test_masked_foldscore_matches_solo(payload):
    cfg = payload.fold_cfg
    rng = np.random.default_rng(3)
    lens, splits = [9, 12, 16], [6, 8, 12]
    seqs, rows = mixed_rows(rng, lens, 16)
    tgt = rng.normal(size=(3, 16)).astype(np.float32)
    m = prot.foldscore_fwd_masked(
        payload.fold_params, seqs, tgt, np.array(lens, np.int32),
        np.array(splits, np.int32), cfg)
    for i, (L, s) in enumerate(zip(lens, splits)):
        solo = prot.foldscore_fwd(payload.fold_params, rows[i][None],
                                  tgt[i][None], cfg, chain_split=s)
        np.testing.assert_allclose(m.plddt[i], solo.plddt[0], atol=ATOL)
        np.testing.assert_allclose(m.ptm[i], solo.ptm[0], atol=ATOL)
        np.testing.assert_allclose(m.pae[i], solo.pae[0], atol=ATOL)


def test_masked_progen_logprobs_match_solo(payload):
    cfg = payload.gen_cfg
    rng = np.random.default_rng(4)
    lens = [7, 10, 12]
    seqs, rows = mixed_rows(rng, lens, 12)
    bb = rng.normal(size=(3, cfg.frontend_seq, 16)).astype(np.float32)
    lp = prot.progen_logprobs(payload.gen_params, bb, seqs, cfg,
                              seq_lens=np.array(lens, np.int32))
    for i, L in enumerate(lens):
        solo = prot.progen_logprobs(payload.gen_params, bb[i][None],
                                    rows[i][None], cfg)
        np.testing.assert_allclose(lp[i], solo[0], atol=ATOL)


def test_predict_batch_masked_matches_per_row_predict(payload, submesh):
    """The acceptance-criterion equivalence: a padded mixed-length
    predict_batch returns metrics bit-close to each row scored alone (via
    the seed ``predict`` task fn) at its true length."""
    rng = np.random.default_rng(5)
    lens, splits = [10, 13, 16, 16], [6, 9, 12, 11]
    seqs, rows = mixed_rows(rng, lens, 16)
    tgt = rng.normal(size=16).astype(np.float32)
    out = payload.predict_batch(submesh, {
        "sequences": seqs, "target": tgt, "receptor_len": splits[0],
        "seq_lens": np.array(lens, np.int32),
        "chain_splits": np.array(splits, np.int32)})
    assert out["batch"]["len_occupancy"] == pytest.approx(
        sum(lens) / (4 * 16))
    for i, (L, s) in enumerate(zip(lens, splits)):
        solo = payload.predict(submesh, {
            "sequence": rows[i], "target": tgt, "receptor_len": s})
        for k in ("plddt", "ptm", "pae"):
            assert out["rows"][i][k] == pytest.approx(solo[k], abs=ATOL)


def test_predict_batch_legacy_has_no_len_padding(payload, submesh):
    """Without seq_lens the payload takes the exact path (len_occupancy 1,
    chain_split static) — homogeneous campaigns stay on seed behavior."""
    rng = np.random.default_rng(6)
    seqs = rng.integers(1, 20, size=(2, 10)).astype(np.int32)
    tgt = rng.normal(size=16).astype(np.float32)
    out = payload.predict_batch(submesh, {
        "sequences": seqs, "target": tgt, "receptor_len": 7})
    assert out["batch"]["len_occupancy"] == 1.0


def test_generate_batch_masked_composition_independent(payload, submesh):
    """A masked row's samples depend only on (seed, bucket length) — never
    on which other rows share the device batch — and are truncated to the
    row's true length."""
    rng = np.random.default_rng(7)
    bbs = rng.normal(size=(3, 8, 16)).astype(np.float32)
    fused = payload.generate_batch(submesh, {
        "backbones": bbs, "seeds": [11, 22, 33], "n": 2, "length": 12,
        "row_lens": [9, 12, 10]})
    assert fused["batch"]["len_occupancy"] == pytest.approx(31 / 36)
    for r, L in enumerate([9, 12, 10]):
        solo = payload.generate_batch(submesh, {
            "backbones": bbs[r][None], "seeds": [[11, 22, 33][r]],
            "n": 2, "length": 12, "row_lens": [L]})
        assert fused["rows"][r][0].shape == (2, L)
        np.testing.assert_array_equal(fused["rows"][r][0], solo["rows"][0][0])
        np.testing.assert_allclose(fused["rows"][r][1], solo["rows"][0][1],
                                   atol=ATOL)


# -- coalesce rules over heterogeneous lengths -------------------------------


def mk_predict_task(rng, n_rows, L, split, masked):
    p = {"sequences": rng.integers(1, 20, size=(n_rows, L)).astype(np.int32),
         "target": rng.normal(size=16).astype(np.float32),
         "receptor_len": split}
    if masked:
        p["seq_lens"] = np.full(n_rows, L, np.int32)
        p["chain_splits"] = np.full(n_rows, split, np.int32)
    return Task(kind="predict_batch", payload=p)


def test_predict_rule_fuses_heterogeneous_lengths():
    rule = predict_batch_coalesce_rule(length_buckets=(16,))
    rng = np.random.default_rng(8)
    a = mk_predict_task(rng, 2, 12, 8, masked=True)
    b = mk_predict_task(rng, 3, 16, 11, masked=True)
    c = mk_predict_task(rng, 2, 14, 9, masked=True)
    assert rule.key(a) == rule.key(b) == rule.key(c) == ("masked", 16, None)
    fused = rule.merge([a, b, c])
    assert fused["sequences"].shape == (7, 16)
    np.testing.assert_array_equal(fused["seq_lens"],
                                  [12, 12, 16, 16, 16, 14, 14])
    np.testing.assert_array_equal(fused["chain_splits"],
                                  [8, 8, 11, 11, 11, 9, 9])
    # member stacks were zero-padded into the bucket, real tokens intact
    np.testing.assert_array_equal(fused["sequences"][0][:12],
                                  a.payload["sequences"][0])
    assert not fused["sequences"][0][12:].any()
    # split fans the fused rows back out per member
    result = {"rows": [{"i": i} for i in range(7)], "batch": {"rows": 7}}
    outs = rule.split([a, b, c], result)
    assert [len(o["rows"]) for o in outs] == [2, 3, 2]
    assert outs[1]["rows"][0] == {"i": 2}
    assert outs[0]["batch"]["leader"] and not outs[1]["batch"]["leader"]


def test_predict_rule_legacy_and_masked_never_fuse():
    rule = predict_batch_coalesce_rule(length_buckets=(16,))
    rng = np.random.default_rng(9)
    legacy = mk_predict_task(rng, 2, 16, 11, masked=False)
    masked = mk_predict_task(rng, 2, 16, 11, masked=True)
    assert rule.key(legacy) != rule.key(masked)
    # legacy keys stay the exact (L, split) — the seed behavior
    assert rule.key(legacy) == (16, 11, None)
    # legacy-only merges produce the seed payload shape (no seq_lens)
    fused = rule.merge([legacy, mk_predict_task(rng, 1, 16, 11, False)])
    assert "seq_lens" not in fused and "chain_splits" not in fused


def mk_gen_task(rng, P, L, seed, masked, buckets=(12,)):
    p = {"backbones": rng.normal(size=(1, P, 16)).astype(np.float32),
         "seeds": [seed], "n": 2, "length": L, "temperature": 1.0}
    if masked:
        p["length"] = bucket_len(L, buckets)
        p["row_lens"] = [L]
    return Task(kind="generate_batch", payload=p)


def test_generate_rule_masked_fuses_across_backbone_lengths():
    rule = generate_batch_coalesce_rule(prefix_len=8)
    rng = np.random.default_rng(10)
    a = mk_gen_task(rng, 14, 10, 1, masked=True)
    b = mk_gen_task(rng, 16, 12, 2, masked=True)
    # different backbone lengths, same bucket: identical masked keys
    assert rule.key(a) == rule.key(b)
    fused = rule.merge([a, b])
    assert fused["backbones"].shape == (2, 8, 16)   # prefix-trimmed
    np.testing.assert_array_equal(fused["row_lens"], [10, 12])
    assert fused["length"] == 12
    # legacy one-row tasks with different backbone shapes keep distinct
    # keys (the seed behavior — shape is part of compatibility)
    la = mk_gen_task(rng, 14, 12, 3, masked=False)
    lb = mk_gen_task(rng, 16, 12, 4, masked=False)
    assert rule.key(la) != rule.key(lb)
    assert rule.key(la) != rule.key(a)


# -- metrics_rows vectorization ---------------------------------------------


def test_metrics_rows_matches_scalar_indexing():
    m = prot.FoldMetrics(plddt=np.array([50.5, 60.25], np.float32),
                         ptm=np.array([0.5, 0.75], np.float32),
                         pae=np.array([10.0, 12.5], np.float32))
    rows = prot.metrics_rows(m)
    assert rows == [{"plddt": 50.5, "ptm": 0.5, "pae": 10.0},
                    {"plddt": 60.25, "ptm": 0.75, "pae": 12.5}]
    assert all(isinstance(v, float) for r in rows for v in r.values())
    assert prot.metrics_rows(m, 1) == rows[:1]


# -- end to end --------------------------------------------------------------


def test_mixed_length_campaign_end_to_end():
    """A mixed-receptor-length campaign (batched scoring + batched
    sampling) completes with dense masked fusion and no failed tasks."""
    spec = CampaignSpec(
        structures=4, receptor_len=(10, 12, 14, 16), peptide_len=4,
        protocols=(ProtocolSpec("im-rp", n_cycles=2, n_candidates=4,
                                score_batch=4, generate_batch_size=8),),
        max_workers=4, seed=0)
    with ImpressSession(spec) as sess:
        assert sess.length_buckets is not None
        rep = sess.run(timeout=300)
    assert rep["executor"]["n_failed"] == 0
    assert rep.trajectories > 0
    assert rep["len_occupancy"] is not None
    assert 0.5 < rep["len_occupancy"] <= 1.0
    assert rep["gen_len_occupancy"] is not None
    assert rep["compile"]["length_buckets"] == list(sess.length_buckets)


def _restore_cache_config(saved):
    from jax.experimental.compilation_cache import compilation_cache
    for knob, val in saved.items():
        jax.config.update(knob, val)
    compilation_cache.reset_cache()


_CACHE_KNOBS = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_entry_size_bytes",
                "jax_persistent_cache_min_compile_time_secs")


def test_compilation_cache_opt_in(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR unset, enable_compilation_cache()
    puts XLA's persistent cache at the fixed in-checkout directory, and a
    campaign report records that effective directory. (The campaign's
    cache is redirected to a temporary directory, so the test writes
    nothing into the checkout.)"""
    assert DEFAULT_COMPILATION_CACHE == os.path.join(REPO_ROOT, ".jax_cache")
    monkeypatch.setattr(session_mod, "DEFAULT_COMPILATION_CACHE",
                        str(tmp_path / ".jax_cache"))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    saved = {k: getattr(jax.config, k) for k in _CACHE_KNOBS}
    jax.config.update("jax_compilation_cache_dir", None)
    spec = CampaignSpec(
        structures=1, receptor_len=8, peptide_len=4,
        protocols=(ProtocolSpec("im-rp", n_cycles=1, n_candidates=2),),
        max_workers=2)
    try:
        with ImpressSession(spec) as sess:
            # a session alone leaves the cache off
            assert sess.run(timeout=120)["compile"][
                "persistent_cache_dir"] is None
        path = enable_compilation_cache()
        assert path == str(tmp_path / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        with ImpressSession(spec) as sess:
            rep = sess.run(timeout=120)
    finally:
        _restore_cache_config(saved)
    assert rep["compile"]["persistent_cache_dir"] == path


def test_compilation_cache_honours_env(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's own setting is the cache
    directory and the helper sets no other path (a fresh process, since
    JAX reads the variable when it is imported)."""
    cache = str(tmp_path / "xla-cache")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache,
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO_ROOT, "src"))
    code = ("import jax; from repro.session import enable_compilation_cache;"
            " print(enable_compilation_cache());"
            " print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [cache, cache]
