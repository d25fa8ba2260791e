"""A whole run of the gateway cell at a tiny size on the CPU (HTTP
server, load client in a child process, drain), with the chip check
skipped: ``correct`` holds on the program as it is and comes out false
when the sequence-design stage alters a token where it is produced."""

import numpy as np
import pytest

from bench import harness

CELL = "gateway-tiny"


def _alter_tokens(monkeypatch):
    from repro.core.payload import ProteinPayload
    orig = ProteinPayload.generate_batch

    def generate_batch(self, submesh, payload):
        out = orig(self, submesh, payload)
        rows = []
        for seqs, lls in out["rows"]:
            seqs = np.array(seqs)
            seqs[:, 0] = seqs[:, 0] % 20 + 1
            rows.append((seqs, lls))
        return dict(out, rows=rows)

    monkeypatch.setattr(ProteinPayload, "generate_batch", generate_batch)


@pytest.mark.parametrize("fault", [None, _alter_tokens])
def test_gateway_run(tree, no_compile_cache, monkeypatch, fault):
    if fault is not None:
        fault(monkeypatch)
    res = harness.run(CELL, 31337, 3.0, 0, root=tree,
                      bench_dir=tree + "/bench", require_tpu=False)
    assert res["attempted"] == 6 and res["failed"] == 0
    m = res["metrics"]
    assert set(m) == {"designs_per_s", "campaign_p95_s", "setup_s"}
    assert all(v["value"] > 0 for v in m.values())
    assert res["correct"] is (fault is None), res["checks"]
