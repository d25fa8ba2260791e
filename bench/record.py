"""What the timed path produced, recorded where the protocols receive it.

``Recorder.wrap(protocol)`` wraps the protocol's completion handlers (the
``DesignProtocol.handlers`` registry the coordinator routes every finished
task through) and its pipeline constructors. Each wrapper notes what the
pipeline asked for and what came back, then calls the original handler
unchanged:

- ``generate_batch``: the backbone the pipeline sampled on and the
  candidates (tokens, log-likelihood) its own row returned;
- ``predict_batch``: the complexes the pipeline sent (candidate + target
  peptide, target descriptor, chain split) and the score row each got;
- every accepted design, with the time it was accepted;
- every pipeline, with the time it was created.

Because the records are taken after the executor split a fused batch back
into its members, a row handed to the wrong pipeline disagrees with the
reference run on that pipeline's own inputs.
"""

from __future__ import annotations

import threading
import time

import numpy as np


class Recorder:
    def __init__(self, prefix_len: int):
        self.prefix_len = int(prefix_len)
        self.clock = time.monotonic
        self._lock = threading.Lock()
        self.gen = []        # dicts: t, uid, ns, backbone, tokens, ll
        self.scores = []     # dicts: t, uid, ns, seq, target, split, metrics
        self.accepted = []   # (t, uid)
        self.created = {}    # uid -> t

    # -- wiring ------------------------------------------------------------

    def wrap(self, proto):
        """Record everything ``proto`` receives from now on."""
        gen_ns, fold_ns = "default", "default"
        for s in getattr(proto, "stage_specs", lambda: ())():
            if s.kind == "generate_batch":
                gen_ns = s.params
            elif s.kind == "predict_batch":
                fold_ns = s.params
        h = proto.handlers
        for kind, fn in list(h.items()):
            h[kind] = self._handler(kind, fn, gen_ns, fold_ns)
        for name in ("new_pipeline", "spawn_pipeline"):
            orig = getattr(proto, name, None)
            if orig is not None:
                setattr(proto, name, self._creator(orig))
        return proto

    def _creator(self, orig):
        def create(*a, **kw):
            pl = orig(*a, **kw)
            if pl is not None:
                with self._lock:
                    self.created[pl.uid] = self.clock()
            return pl
        return create

    def _handler(self, kind, fn, gen_ns, fold_ns):
        def handle(pl, result):
            t = self.clock()
            if kind == "generate_batch":
                self._note_generate(t, pl, result, gen_ns)
            elif kind == "predict_batch":
                self._note_scores(t, pl, result, fold_ns)
            decision = fn(pl, result)
            if getattr(decision, "accepted_design", None) is not None:
                with self._lock:
                    self.accepted.append((self.clock(), pl.uid))
            return decision
        return handle

    # -- notes -------------------------------------------------------------

    def _note_generate(self, t, pl, result, ns):
        (seqs, lls), = result["rows"]
        bb = np.array(pl.meta["backbone"][:self.prefix_len], np.float32)
        rec = {"t": t, "uid": pl.uid, "ns": ns, "backbone": bb,
               "tokens": [np.array(s, np.int32) for s in seqs],
               "ll": np.array(lls, np.float64)}
        with self._lock:
            self.gen.append(rec)

    def _note_scores(self, t, pl, result, ns):
        seqs, _ = pl.meta["candidates"]
        i = int(pl.meta["cand_idx"])
        pep = np.asarray(pl.meta["peptide_tokens"], np.int32)
        tgt = np.array(pl.meta["target"], np.float32)
        split = int(pl.meta["receptor_len"])
        recs = []
        for r, row in enumerate(result["rows"]):
            seq = np.concatenate([np.asarray(seqs[i + r], np.int32), pep])
            recs.append({"t": t, "uid": pl.uid, "ns": ns, "seq": seq,
                         "target": tgt, "split": split,
                         "metrics": (float(row["plddt"]), float(row["ptm"]),
                                     float(row["pae"]))})
        with self._lock:
            self.scores.extend(recs)

    # -- reading -----------------------------------------------------------

    def designs(self, t0: float, t1: float):
        """Accepted designs in [t0, t1] with their cycle times: the time
        since the pipeline's previous accepted design, or since it was
        created."""
        with self._lock:
            acc = sorted(self.accepted)
            created = dict(self.created)
        last, out = {}, []
        for t, uid in acc:
            prev = last.get(uid, created.get(uid))
            last[uid] = t
            if t0 <= t <= t1 and prev is not None:
                out.append(t - prev)
        return out
