"""A whole run of the session cell at a tiny size on the CPU, with the
chip check skipped: the comparison passes on the program as it is, the
control fails it, and a token or a score altered where it is produced
makes ``correct`` false."""

import numpy as np
import pytest

from bench import check, harness

CELL = "imrp-tiny"


def _run(tree, seed=20251016):
    return harness.run(CELL, seed, 3.0, 0, root=tree,
                       bench_dir=tree + "/bench", require_tpu=False)


def test_program_passes_and_control_fails(tree, no_compile_cache):
    r = harness.Run(CELL, 987654321987, 3.0, False, root=tree,
                    bench_dir=tree + "/bench", require_tpu=False)
    e2e = r.execute()
    assert e2e["failed"] == 0 and e2e["designs_per_s"] > 0
    limits = r.config["limits"]
    prog = r.compare()
    assert set(prog) == set(limits)
    assert all(prog[k] <= limits[k] for k in limits), prog
    words = np.random.SeedSequence([r.seed, 4]).generate_state(
        2, dtype=np.uint32)
    ctrl = check.compare(r.recorder, r.weights, r.roles(),
                         r.config["check"], words, control=True)
    assert any(ctrl[k] > limits[k] for k in limits), ctrl


def _alter_tokens(monkeypatch):
    from repro.models.protein import PagedDecodeEngine
    orig = PagedDecodeEngine.run

    def run(self, *a, **kw):
        out = orig(self, *a, **kw)
        for tag, (toks, ll) in out.items():
            toks = np.array(toks)
            toks[0] = toks[0] % 20 + 1
            out[tag] = (toks, ll)
        return out

    monkeypatch.setattr(PagedDecodeEngine, "run", run)


def _alter_scores(monkeypatch):
    from repro.models import protein as prot
    orig = prot.metrics_rows

    def metrics_rows(m, n=None):
        return [dict(r, plddt=r["plddt"] + 5.0) for r in orig(m, n)]

    monkeypatch.setattr(prot, "metrics_rows", metrics_rows)


@pytest.mark.parametrize("fault,number", [
    (_alter_tokens, "gen_ll_gap"),
    (_alter_scores, "score_gap"),
])
def test_altered_answer_is_not_correct(tree, no_compile_cache, monkeypatch,
                                       fault, number):
    fault(monkeypatch)
    res = _run(tree)
    assert res["correct"] is False
    c = res["checks"][number]
    assert c["value"] > c["limit"]
    assert list(res)[-1] == "checks"
