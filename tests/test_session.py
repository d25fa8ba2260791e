"""Campaign API tests: the ImpressSession facade, the DesignProtocol typed
routing registry, multi-protocol coordination on one executor, protocol
pluggability without coordinator edits, routing equivalence of the legacy
constructor vs the new registry path, and the session-level checkpoint
round-trip."""

import json

import numpy as np
import pytest

import jax

from repro.core import (Coordinator, Decision, DesignProtocol,
                        ImpressProtocol, MultiObjectiveConfig,
                        MultiObjectiveProtocol, Pipeline, ProtocolConfig,
                        ResourceRequest, Task)
from repro.core.multi_objective import dominates
from repro.runtime import AsyncExecutor, DeviceAllocator
from repro.session import (CampaignReport, CampaignSpec, ImpressSession,
                           ProtocolSpec, register_protocol)


class FakePayload:
    """Deterministic instant payloads (no devices touched)."""

    def __init__(self, seed=0):
        self.rng = np.random.default_rng(seed)

    def generate(self, submesh, payload):
        n, L = payload["n"], payload["length"]
        seqs = self.rng.integers(1, 21, size=(n, L)).astype(np.int32)
        return seqs, -self.rng.random(n).astype(np.float32)

    def predict(self, submesh, payload):
        s = float(np.mean(payload["sequence"])) + self.rng.normal(0, 2.0)
        return {"plddt": 50 + s, "ptm": 0.5, "pae": 15.0}


def fake_executor(seed=0, max_workers=2):
    ex = AsyncExecutor(DeviceAllocator(jax.devices()),
                       max_workers=max_workers)
    fp = FakePayload(seed)
    ex.register("generate", fp.generate)
    ex.register("predict", fp.predict)
    return ex


def impress(seed=0, **kw):
    kw.setdefault("n_candidates", 4)
    kw.setdefault("n_cycles", 2)
    kw.setdefault("gen_devices", 1)
    kw.setdefault("predict_devices", 1)
    kw.setdefault("max_sub_pipelines", 2)
    return ImpressProtocol(ProtocolConfig(seed=seed, **kw))


def new_pl(p, name="X"):
    return p.new_pipeline(name, np.zeros((30, 16), np.float32),
                          np.zeros(16, np.float32), 24,
                          np.arange(1, 7, dtype=np.int32))


# ---------------------------------------------------------------------------
# typed routing registry
# ---------------------------------------------------------------------------

def test_impress_declares_typed_handler_registry():
    p = impress()
    assert set(p.task_kinds()) == {"generate", "generate_batch",
                                   "predict", "predict_batch"}
    pl = new_pl(p)
    seqs = np.tile(np.arange(24, dtype=np.int32), (4, 1))
    d = p.handlers["generate"](pl, (seqs, -np.arange(4, dtype=np.float32)))
    assert isinstance(d, Decision)
    assert len(d.tasks) == 1 and d.tasks[0].kind == "predict"
    d = p.handlers["predict"](pl, {"plddt": 80.0, "ptm": 0.8, "pae": 8.0})
    assert d.events == [{"event": "accepted", "cycle": 1}]
    assert d.accepted_design is pl.history[-1]   # §V training data declared


def test_legacy_constructor_and_registry_routing_are_event_identical():
    """Acceptance: the seed path (score_batch=0, generate_batch_size=0,
    single protocol) produces the identical event sequence whether the
    protocol is bound through the legacy ``Coordinator(ex, proto)`` shim
    or the explicit ``add_protocol`` registry (sequential max_inflight=1
    makes completion order deterministic)."""
    def run(legacy):
        ex = fake_executor(seed=7, max_workers=2)
        proto = impress(seed=7, n_cycles=3, n_candidates=5)
        if legacy:
            coord = Coordinator(ex, proto, max_inflight=1)
        else:
            coord = Coordinator(ex)
            coord.add_protocol(proto, max_inflight=1)
        for i in range(3):
            coord.add_pipeline(new_pl(proto, f"P{i}"))
        rep = coord.run(timeout=60)
        ex.shutdown()
        return rep

    rep_legacy, rep_registry = run(True), run(False)
    strip = lambda evs: [(e["event"], e.get("pipeline"), e.get("cycle"))
                         for e in evs]
    assert strip(rep_legacy["events"]) == strip(rep_registry["events"])
    # single-protocol events carry no protocol tag (seed-identical stream)
    assert all("protocol" not in e for e in rep_legacy["events"])
    assert all("protocol" not in e for e in rep_registry["events"])


# ---------------------------------------------------------------------------
# protocol pluggability: a new protocol never touches coordinator.py
# ---------------------------------------------------------------------------

class TakeFirstProtocol(DesignProtocol):
    """Minimal third-party protocol: generate once, score the top
    candidate, always accept — written only against the DesignProtocol
    interface."""

    def __init__(self):
        self.handlers = {"generate": self._gen_done,
                         "predict": self._pred_done}

    def new_pipeline(self, name, backbone, target, receptor_len,
                     peptide_tokens=None, **kw):
        return Pipeline(name=name, meta={
            "backbone": np.asarray(backbone, np.float32),
            "target": np.asarray(target, np.float32),
            "receptor_len": int(receptor_len), "trajectories": 0})

    def first_task(self, pl):
        return Task(kind="generate", pipeline_id=pl.uid, payload={
            "backbone": pl.meta["backbone"], "n": 2,
            "length": pl.meta["receptor_len"], "seed": 0,
        }, resources=ResourceRequest(n_devices=1))

    def _gen_done(self, pl, result):
        seqs, lls = result
        pl.meta["best"] = np.asarray(seqs[int(np.argmax(lls))], np.int32)
        return Decision(tasks=[Task(
            kind="predict", pipeline_id=pl.uid, payload={
                "sequence": pl.meta["best"], "target": pl.meta["target"],
                "receptor_len": pl.meta["receptor_len"],
            }, resources=ResourceRequest(n_devices=1))])

    def _pred_done(self, pl, metrics):
        pl.meta["trajectories"] += 1
        pl.history.append(dict(metrics, fitness=1.0, cycle=pl.cycle,
                               gen_version=0))
        pl.active = False
        return Decision(events=[{"event": "completed", "cycle": 0}],
                        accepted_design=pl.history[-1])


def test_custom_protocol_runs_through_unmodified_coordinator():
    ex = fake_executor()
    proto = TakeFirstProtocol()
    coord = Coordinator(ex)
    coord.add_protocol(proto, name="take-first")
    coord.add_pipeline(new_pl(proto, "T0"))
    rep = coord.run(timeout=30)
    ex.shutdown()
    assert rep["trajectories"] == 1
    assert [e["event"] for e in rep["events"]] == ["completed"]
    assert rep["protocols"]["take-first"]["n_pipelines"] == 1


def test_coordinator_handle_s_counts_completions():
    """``coordinator.handle_s``, in the executor's registry, observes once
    for every completion the coordinator routes."""
    ex = fake_executor(seed=5)
    proto = impress(seed=5, n_cycles=2, n_candidates=3)
    coord = Coordinator(ex)
    coord.add_protocol(proto)
    routed = []
    handle = coord._handle

    def counted(task):
        routed.append(task.uid)
        return handle(task)

    coord._handle = counted
    for i in range(2):
        coord.add_pipeline(new_pl(proto, f"H{i}"))
    coord.run(timeout=60)
    ex.shutdown()
    h = ex.telemetry.metrics.histogram("coordinator.handle_s")
    assert len(routed) >= 4 and h.count == len(routed)
    assert h.sum > 0


def test_multi_objective_pareto_rule():
    assert dominates([2, 2, 2], [1, 2, 2])
    assert not dominates([1, 2, 2], [2, 2, 2])
    assert not dominates([2, 1, 1], [1, 2, 2])   # trade-off: no dominance
    p = MultiObjectiveProtocol(MultiObjectiveConfig(
        n_candidates=3, n_cycles=4, max_declines=1))
    pl = new_pl(p)
    seqs = np.tile(np.arange(24, dtype=np.int32), (3, 1))
    p.handlers["generate"](pl, (seqs, -np.arange(3, dtype=np.float32)))
    good = {"plddt": 80.0, "ptm": 0.8, "pae": 8.0}
    d = p.handlers["predict"](pl, good)        # empty front: accepted
    assert d.events[0]["event"] == "accepted" and pl.cycle == 1
    p.handlers["generate"](pl, (seqs, -np.arange(3, dtype=np.float32)))
    worse = {"plddt": 70.0, "ptm": 0.7, "pae": 10.0}   # dominated
    d = p.handlers["predict"](pl, worse)
    assert d.events[0]["event"] == "reselect"
    tradeoff = {"plddt": 90.0, "ptm": 0.5, "pae": 9.0}  # non-dominated
    d = p.handlers["predict"](pl, tradeoff)
    assert d.events[0]["event"] == "accepted" and len(pl.meta["front"]) == 2
    assert d.accepted_design is pl.history[-1]


# ---------------------------------------------------------------------------
# session facade: multi-protocol campaigns on one executor
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def multi_report_and_state():
    spec = CampaignSpec(
        structures=1, receptor_len=12, max_workers=4, seed=3,
        protocols=(ProtocolSpec("im-rp", n_candidates=3, n_cycles=2,
                                max_sub_pipelines=1),
                   ProtocolSpec("cont-v", n_candidates=3, n_cycles=2),
                   ProtocolSpec("multi-objective", n_candidates=3,
                                n_cycles=2)))
    with ImpressSession(spec) as sess:
        report = sess.run(timeout=240)
        state = sess.checkpoint()
    return report, state


def test_session_runs_three_protocols_concurrently(multi_report_and_state):
    """Acceptance: one ImpressSession run executes IM-RP and CONT-V (and
    the multi-objective demo) concurrently on one executor."""
    report, _ = multi_report_and_state
    assert isinstance(report, CampaignReport)
    assert report.schema_version == 1
    assert set(report.protocols) == {"im-rp", "cont-v", "multi-objective"}
    for name, p in report.protocols.items():
        assert p["n_pipelines"] == 1, name
        assert p["trajectories"] >= 2, name
        assert p["cycles"], name
    assert report.executor["n_failed"] == 0
    # multi-protocol events are tagged with their binding name
    tags = {e.get("protocol") for e in report.events}
    assert {"im-rp", "cont-v", "multi-objective"} <= tags
    # the control is sub-pipeline free
    assert report.protocols["cont-v"]["n_sub_pipelines"] == 0
    # dict-style back-compat reads from the raw coordinator report
    assert report["n_pipelines"] == report.n_pipelines


def test_session_checkpoint_restore_roundtrip(multi_report_and_state):
    """Satellite: state_dict/load_state_dict through
    ImpressSession.checkpoint()/restore() with a multi-protocol run."""
    report, state = multi_report_and_state
    payload = json.loads(json.dumps(state))   # must survive JSON
    assert payload["schema_version"] == 1
    assert set(payload["coordinator"]["protocols"]) == \
        {"im-rp", "cont-v", "multi-objective"}

    restored = ImpressSession.from_checkpoint(payload)
    try:
        names = sorted(p.name for p in restored.coordinator.pipelines.values())
        orig = sorted(r["name"] for r in payload["coordinator"]["pipelines"])
        assert names == orig
        # per-protocol state round-trips (spawn counters etc.)
        for name, proto in restored.protocols.items():
            assert proto.state_dict() == \
                payload["coordinator"]["protocols"][name]
        # completed pipelines restore inactive: a fresh run adds no work
        rep2 = restored.run(timeout=60)
        assert rep2.trajectories == report.trajectories
        histories = sorted(
            (p.name, len(p.history))
            for p in restored.coordinator.pipelines.values())
        assert all(n >= 0 for _, n in histories)
        assert sum(n for _, n in histories) == sum(
            len(r["history"]) for r in payload["coordinator"]["pipelines"])
    finally:
        restored.shutdown()


def test_session_validates_protocol_kinds_and_handlers():
    with pytest.raises(ValueError, match="unknown protocol kind"):
        ImpressSession(CampaignSpec(protocols=("no-such-kind",),
                                    receptor_len=12))

    class Unroutable(TakeFirstProtocol):
        def __init__(self):
            super().__init__()
            self.handlers = dict(self.handlers,
                                 fold_and_dock=lambda pl, r: Decision())

    register_protocol("unroutable-demo", lambda ps, cs: (Unroutable(), None))
    with pytest.raises(ValueError, match="fold_and_dock"):
        ImpressSession(CampaignSpec(protocols=("unroutable-demo",),
                                    receptor_len=12))


def test_session_evolution_wiring():
    """evolution=True attaches buffer/trainer; the trainer sees accepted
    designs declared via Decision.accepted_design."""
    spec = CampaignSpec(structures=1, receptor_len=12, max_workers=2,
                        protocols=(ProtocolSpec("im-rp", n_candidates=3,
                                                n_cycles=2,
                                                max_sub_pipelines=0),),
                        evolution=True, finetune_every=1, min_designs=1,
                        finetune_batch=4, finetune_steps=3)
    with ImpressSession(spec) as sess:
        rep = sess.run(timeout=240)
    assert rep.evolution is not None and rep.evolution["enabled"]
    assert len(sess.buffer) >= 1
    assert rep.executor["n_failed"] == 0
