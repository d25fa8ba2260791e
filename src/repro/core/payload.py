"""Device payload functions for IMPRESS tasks.

Four task kinds run on the sub-mesh a task was allocated:

``generate`` (ProteinMPNN analogue) — samples one pipeline's candidates,
  split across the sub-mesh's devices (independent streams — the closest
  analogue of RP placing independent processes on each GPU).
``generate_batch`` — the continuously-batched form: a (rows, n_candidates,
  L) stack sampled in one jitted call per device, one row per pipeline.
  Rows are keyed per-row (``seeds``), so a row's samples are identical no
  matter which other pipelines' rows share the device batch — coalescing
  and rolling admission cannot perturb results.
``predict`` (AlphaFold analogue) — scores one candidate sequence.
``predict_batch`` — vectorized scoring of a candidate stack.
``finetune`` (``FinetunePayload``) — the §V model-evolution trainer: a
  preemptible data-parallel weighted-NLL train step over accepted designs
  that publishes evolved generator params as a new ``ParamStore`` version.

Generator params are versioned (``ProteinPayload.param_store``): sampling
dispatches snapshot (version, params) once, tag results ``gen_version``,
and cache per-device copies by version.

Both batched kinds pad their batch dim up to a ``BATCH_BUCKETS`` size
(bounding the jit cache) and split the padded stack across the sub-mesh's
devices. Their coalesce rules (``*_coalesce_rule``) let the executor fuse
compatible queued tasks from different pipelines into one device batch.

Length-bucketed masked batching: payloads carrying per-row true lengths
(``seq_lens`` for ``predict_batch``, ``row_lens`` for ``generate_batch``)
take the *masked* path — rows of different sequence lengths are padded to
a ``LENGTH_BUCKETS`` edge (or campaign-derived edges, see
``ProteinPayload.length_buckets``) and scored/sampled in one dense device
batch, with pad positions excluded from every metric
(``foldscore_fwd_masked``) and per-row ``chain_splits`` traced so mixed
receptor lengths share one executable. The masked coalesce keys fuse on
``(bucket_len, ...)`` instead of exact ``(L, chain_split)``, so a
mixed-receptor-length campaign batches densely instead of degenerating to
per-length 1-row dispatches. Legacy payloads (no per-row lengths) keep the
exact-length path bit-for-bit and never fuse with masked ones —
homogeneous campaigns are byte-identical to the seed.

Compiled executables are cached per (kind, device, shape) — the cache-miss
path is the paper's "Exec setup" phase (Fig. 5) and is tracked in
``compile_log`` for the utilization benchmark.
"""

from __future__ import annotations

import threading
import time
import zlib
from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.learn.param_store import ParamStore
from repro.models import protein as prot
# Canonical bucketing lives in the runtime layer (the allocator sizes
# sub-meshes off the same buckets); re-exported here for back-compat.
from repro.runtime.allocator import (BATCH_BUCKETS, LENGTH_BUCKETS,  # noqa: F401
                                     bucket_len, bucket_rows)

compile_log: Dict[str, list] = {"generate": [], "predict": []}

# One record per predict_batch device dispatch: real rows vs padded bucket
# rows, token fill (``len_occupancy`` = real tokens / padded tokens) and
# device fan-out — the occupancy numbers behind report()/benchmarks.
batch_log: List[dict] = []

# Same, for generate_batch dispatches; a paged dispatch adds the decode
# steps it ran, the sums over them of active slots and of the live pages
# the attention kernel fetched, and the host time they took (``steps``,
# ``slot_steps``, ``page_steps``, ``step_host_s``), beside its slot count
# (``bucket``).
gen_batch_log: List[dict] = []

# Same, for backbone_batch dispatches (the staged protocols' first stage).
backbone_log: List[dict] = []


def _named(fn, **kw):
    """``partial(fn, **kw)`` under ``fn``'s name, so its jitted executable
    reads ``jit_<fn>`` in a profile rather than ``jit__unknown``."""
    p = partial(fn, **kw)
    p.__name__ = fn.__name__
    return p


def _pad_rows(arrs: List[np.ndarray], rows: int):
    """Pad each array's leading dim from ``rows`` up to its bucket size by
    repeating the last real row (dropped again before results return).
    Returns (padded arrays, bucket)."""
    B = bucket_rows(rows)
    if B > rows:
        arrs = [np.concatenate([a, np.repeat(a[-1:], B - rows, 0)])
                for a in arrs]
    return arrs, B


def _split_devices(submesh, bucket: int):
    """Largest even split of ``bucket`` rows across the sub-mesh's devices.
    Returns (devices to use, rows per device)."""
    devices = list(submesh.devices.flat)
    ndev = min(len(devices), bucket)
    while bucket % ndev:
        ndev -= 1
    return devices[:ndev], bucket // ndev


def _fan_out_rows(tasks, result, n_rows):
    """Shared ``CoalesceRule.split``: slice a fused {"rows", "batch"}
    result back into one per member task, stamping fused/leader so the
    coordinator counts each dispatch's occupancy exactly once. Provenance
    (the dispatch's ``gen_version``) is copied to every member."""
    rows = result["rows"]
    info = result.get("batch", {})
    outs, at = [], 0
    for i, t in enumerate(tasks):
        k = n_rows(t)
        out = {"rows": rows[at:at + k],
               "batch": dict(info, fused=len(tasks),
                             leader=(i == 0))}
        if "gen_version" in result:
            out["gen_version"] = result["gen_version"]
        outs.append(out)
        at += k
    return outs


def _fold_in_keys(seed, n: int) -> np.ndarray:
    """The per-device sampling keys ``fold_in(PRNGKey(seed), i)`` for
    ``i < n``, built in ONE vectorized device call instead of ``n`` eager
    ``fold_in`` dispatches (which cost >1 ms per fused dispatch at n=16) —
    bit-identical to the eager loop, so seeded runs are unchanged."""
    base = jax.random.PRNGKey(int(seed))
    return np.asarray(
        jax.vmap(lambda i: jax.random.fold_in(base, i))(jnp.arange(n)))


class ProteinPayload:
    """Holds generator + scorer params and exposes executor task fns.

    Generator params live behind a versioned ``ParamStore``: model evolution
    publishes evolved params as a new version and generators hot-swap on
    their next dispatch — each generate/generate_batch call snapshots
    ``param_store.current()`` once, so in-flight dispatches finish on the
    version they started with, and every result is tagged ``gen_version``
    for provenance. Per-device param copies are cached *by version*;
    retired versions evict their copies via the store's retire hook."""

    def __init__(self, key=None, gen_cfg=None, fold_cfg=None, length=48,
                 reduced=False, length_buckets=None):
        from repro.configs.registry import get_config, get_reduced
        key = key if key is not None else jax.random.PRNGKey(0)
        kg, kf = jax.random.split(key)
        get = get_reduced if reduced else get_config
        self._reduced = bool(reduced)
        self.gen_cfg = gen_cfg or get("progen-s")
        self.fold_cfg = fold_cfg or get("foldscore-s")
        self.param_store = ParamStore(prot.init_progen(kg, self.gen_cfg))
        self.param_store.on_retire(
            partial(self._drop_gen_versions, "default"))
        self.fold_params = prot.init_foldscore(kf, self.fold_cfg)
        # param-set namespaces (heterogeneous stages): task payloads pick a
        # generator/scorer by ``payload["params"]``; "default" is the
        # original single-model pair, so unstaged campaigns are untouched
        self.gen_stores: Dict[str, ParamStore] = {
            "default": self.param_store}
        self.gen_cfgs: Dict[str, object] = {"default": self.gen_cfg}
        self.fold_sets: Dict[str, Tuple] = {
            "default": (self.fold_cfg, self.fold_params)}
        self.length = length
        # token-dim bucket edges for masked payloads; None = the global
        # LENGTH_BUCKETS table (campaigns pass denser histogram-derived
        # edges via register_all)
        self.length_buckets = (tuple(length_buckets)
                               if length_buckets else None)
        self._cache: Dict[Tuple, callable] = {}
        self._cache_lock = threading.Lock()
        self._retired_versions: set = set()

    # -- param-set namespaces ---------------------------------------------

    def add_generator(self, name: str, key=None, cfg=None) -> ParamStore:
        """Register a second sequence-design param set under ``name``: its
        own versioned ``ParamStore`` (independently evolvable/hot-swappable)
        and optionally its own config. Tasks select it with
        ``payload["params"] == name``. Returns the store."""
        if name in self.gen_stores:
            return self.gen_stores[name]
        from repro.configs.registry import get_config, get_reduced
        cfg = cfg or (get_reduced if self._reduced else get_config)(
            "progen-s")
        # crc32, not hash(): str hashing is salted per process and would
        # make namespace inits differ across runs
        key = key if key is not None else jax.random.PRNGKey(
            zlib.crc32(name.encode()) & 0xFFFF)
        store = ParamStore(prot.init_progen(key, cfg))
        store.on_retire(partial(self._drop_gen_versions, name))
        self.gen_stores[name] = store
        self.gen_cfgs[name] = cfg
        return store

    def add_scorer(self, name: str, key=None, cfg=None):
        """Register a second fold/score param set under ``name`` (e.g. the
        ``foldscore-m`` multimer variant for a binder protocol's fold
        stage). Tasks select it with ``payload["params"] == name``."""
        if name in self.fold_sets:
            return self.fold_sets[name]
        from repro.configs.registry import get_config, get_reduced
        cfg = cfg or (get_reduced if self._reduced else get_config)(
            "foldscore-m")
        key = key if key is not None else jax.random.PRNGKey(
            zlib.crc32(name.encode()) & 0xFFFF)
        self.fold_sets[name] = (cfg, prot.init_foldscore(key, cfg))
        return self.fold_sets[name]

    @property
    def gen_params(self):
        """The current generator params (read-only view of the store)."""
        return self.param_store.current()[1]

    # -- compiled-function cache ----------------------------------------

    def _compiled(self, kind, device, builder):
        key = (kind, device.id)
        with self._cache_lock:
            fn = self._cache.get(key)
        if fn is None:
            t0 = time.monotonic()
            fn = builder()
            with self._cache_lock:
                self._cache[key] = fn
            compile_log.setdefault(kind, []).append(time.monotonic() - t0)
        return fn

    def _params_on(self, which, params, device):
        """Per-device param copy, cached by ``which`` — ``("gen",
        namespace, version)`` for generator params, so stale copies are
        evicted *by version, per namespace* when a store retires one
        (never by cache-key position). A version retired mid-dispatch
        (two publishes inside one dispatch's window) is used uncached: the
        retire hook has already run for it, so a late insert would never
        be evicted again."""
        key = (which, "params", device.id)
        with self._cache_lock:
            p = self._cache.get(key)
        if p is None:
            p = jax.device_put(params, device)
            with self._cache_lock:
                # tombstone check at insert time: the version may have been
                # retired while the device transfer was in flight
                if which not in self._retired_versions:
                    self._cache[key] = p
        return p

    def _drop_gen_versions(self, namespace, versions):
        """ParamStore retire hook (bound per namespace): evict per-device
        copies of the namespace's retired generator versions from the cache
        (and remember them, so an in-flight dispatch can't re-insert one
        after this ran)."""
        with self._cache_lock:
            self._retired_versions.update(
                ("gen", namespace, v) for v in versions)
            stale = [k for k in self._cache
                     if isinstance(k[0], tuple) and k[0][0] == "gen"
                     and k[0][1] == namespace and k[0][2] in versions]
            for k in stale:
                del self._cache[k]

    def _gen_set(self, payload):
        """(namespace, store, cfg, compile-key suffix) for a sampling
        payload — ``payload["params"]`` picks the generator param set."""
        ns = payload.get("params") or "default"
        sfx = "" if ns == "default" else f"@{ns}"
        return ns, self.gen_stores[ns], self.gen_cfgs[ns], sfx

    def _fold_set(self, payload):
        """(namespace, cfg, params, compile-key suffix) for a scoring
        payload — ``payload["params"]`` picks the fold param set."""
        ns = payload.get("params") or "default"
        cfg, params = self.fold_sets[ns]
        return ns, cfg, params, ("" if ns == "default" else f"@{ns}")

    # -- task functions ---------------------------------------------------

    def generate(self, submesh, payload):
        """Sample payload['n'] candidate sequences, split across devices.
        Returns {"seqs" (n,L) np.int32, "lls" (n,) np.float32,
        "gen_version" int}. Per-device keys are packed in one vectorized
        ``fold_in`` call (bit-identical to the former eager per-device
        loop); the generator version is snapshotted once for the whole
        dispatch."""
        n, length = payload["n"], payload["length"]
        temp = payload.get("temperature", 1.0)
        devices = list(submesh.devices.flat)
        per = int(np.ceil(n / len(devices)))
        backbone = np.asarray(payload["backbone"], np.float32)[None]
        ns, store, gcfg, sfx = self._gen_set(payload)
        ver, gparams = store.current()
        keys = _fold_in_keys(payload["seed"], len(devices))
        futures = []
        for i, dev in enumerate(devices):
            take = min(per, n - i * per)
            if take <= 0:
                break
            fn = self._compiled(
                f"generate{take}_L{length}_t{temp}{sfx}", dev,
                lambda take=take: jax.jit(
                    partial(prot.progen_sample, n=take, length=length,
                            cfg=gcfg, temperature=temp)))
            k = jax.device_put(keys[i], dev)
            bb = jax.device_put(backbone[:, :gcfg.frontend_seq], dev)
            gp = self._params_on(("gen", ns, ver), gparams, dev)
            futures.append(fn(gp, bb, key=k))
        seqs = np.concatenate([np.asarray(s[0][0]) for s in futures])[:n]
        lls = np.concatenate([np.asarray(s[1][0]) for s in futures])[:n]
        return {"seqs": seqs.astype(np.int32),
                "lls": lls.astype(np.float32), "gen_version": ver}

    def predict(self, submesh, payload):
        """Score one sequence. Returns {"plddt","ptm","pae"} floats.

        With ``seq_len`` in the payload (the row's true length — set by
        the protocol when length bucketing is active) the sequence is
        padded to its ``length_buckets`` edge and scored by the masked
        kernel under the compile key ``predict_mb1_L{bucket}`` — the same
        executable family a 1-row masked ``predict_batch`` dispatch uses,
        so solo scoring stops minting per-exact-length executables."""
        dev = submesh.devices.flat[0]
        seq = np.asarray(payload["sequence"], np.int32)[None]
        tgt = np.asarray(payload["target"], np.float32)[None]
        split = int(payload["receptor_len"])
        ns, fcfg, fparams, sfx = self._fold_set(payload)
        fp = self._params_on(("fold", ns), fparams, dev)
        if payload.get("seq_len") is not None:
            true_len = int(payload["seq_len"])
            Lb = bucket_len(seq.shape[1], self.length_buckets)
            if Lb > seq.shape[1]:
                seq = np.concatenate(
                    [seq, np.zeros((1, Lb - seq.shape[1]), np.int32)],
                    axis=1)
            fn = self._compiled(
                f"predict_mb1_L{Lb}{sfx}", dev,
                lambda: jax.jit(_named(prot.foldscore_fwd_masked,
                                       cfg=fcfg)))
            m = fn(fp, jax.device_put(seq, dev), jax.device_put(tgt, dev),
                   jax.device_put(np.asarray([true_len], np.int32), dev),
                   jax.device_put(np.asarray([split], np.int32), dev))
        else:
            fn = self._compiled(
                f"predict{seq.shape[1]}_{split}{sfx}", dev,
                lambda: jax.jit(partial(prot.foldscore_fwd,
                                        cfg=fcfg,
                                        chain_split=split)))
            m = fn(fp, jax.device_put(seq, dev), jax.device_put(tgt, dev))
        return {"plddt": float(m.plddt[0]), "ptm": float(m.ptm[0]),
                "pae": float(m.pae[0])}

    def predict_batch(self, submesh, payload):
        """Score a stack of sequences in one vectorized call per device.

        payload: sequences (R, L) i32; target (16,) shared or (R, 16)
        per-row; receptor_len int. The batch dim is padded up to a
        ``BATCH_BUCKETS`` size (pad rows repeat the last real row, are
        dropped before returning, and cannot perturb real rows —
        ``foldscore_fwd`` has no cross-batch mixing) and the padded stack is
        split evenly across the sub-mesh's devices, so large batches run as
        wide as the allocation allows instead of pinning to one device.

        Masked mixed-length form: with per-row ``seq_lens`` (and optional
        per-row ``chain_splits``, defaulting to ``receptor_len``), the
        token dim is padded up to a ``length_buckets`` edge and the stack
        is scored by ``foldscore_fwd_masked`` — pad positions are excluded
        from every metric, and per-row chain splits are traced, so rows of
        different receptor lengths share one dense executable. The jit
        cache stays bounded at |row buckets| × |length buckets|.

        Returns {"rows": [per-row metric dicts], "batch": occupancy info
        incl. ``len_occupancy`` = real tokens / padded tokens}.
        """
        seqs = np.asarray(payload["sequences"], np.int32)
        if seqs.ndim == 1:
            seqs = seqs[None]
        R, L = seqs.shape
        ns, fcfg, fparams, sfx = self._fold_set(payload)
        tgt = np.asarray(payload["target"], np.float32)
        if tgt.ndim == 1:
            tgt = np.tile(tgt[None], (R, 1))
        seq_lens = payload.get("seq_lens")
        masked = seq_lens is not None
        if masked:
            seq_lens = np.asarray(seq_lens, np.int32).reshape(-1)
            splits = np.asarray(
                payload.get("chain_splits",
                            np.full(R, int(payload["receptor_len"]))),
                np.int32).reshape(-1)
            Lb = bucket_len(L, self.length_buckets)
            if Lb > L:
                seqs = np.concatenate(
                    [seqs, np.zeros((R, Lb - L), np.int32)], axis=1)
                L = Lb
            len_occ = float(seq_lens.sum()) / float(R * L)
            (seqs, tgt, seq_lens, splits), B = _pad_rows(
                [seqs, tgt, seq_lens, splits], R)
        else:
            split = int(payload["receptor_len"])
            len_occ = 1.0
            (seqs, tgt), B = _pad_rows([seqs, tgt], R)
        devices, per = _split_devices(submesh, B)
        ndev = len(devices)
        futures = []
        for i, dev in enumerate(devices):
            sl = slice(i * per, (i + 1) * per)
            fp = self._params_on(("fold", ns), fparams, dev)
            s = jax.device_put(seqs[sl], dev)
            t = jax.device_put(tgt[sl], dev)
            if masked:
                fn = self._compiled(
                    f"predict_mb{per}_L{L}{sfx}", dev,
                    lambda: jax.jit(_named(prot.foldscore_fwd_masked,
                                           cfg=fcfg)))
                futures.append(fn(fp, s, t,
                                  jax.device_put(seq_lens[sl], dev),
                                  jax.device_put(splits[sl], dev)))
            else:
                fn = self._compiled(
                    f"predict_b{per}_L{L}_{split}{sfx}", dev,
                    lambda: jax.jit(partial(prot.foldscore_fwd,
                                            cfg=fcfg,
                                            chain_split=split)))
                futures.append(fn(fp, s, t))
        m = prot.FoldMetrics(
            plddt=np.concatenate([np.asarray(f.plddt) for f in futures]),
            ptm=np.concatenate([np.asarray(f.ptm) for f in futures]),
            pae=np.concatenate([np.asarray(f.pae) for f in futures]))
        batch = {"rows": R, "bucket": B, "occupancy": R / B, "devices": ndev,
                 "len_occupancy": len_occ}
        batch_log.append(batch)
        return {"rows": prot.metrics_rows(m, R), "batch": dict(batch)}

    def _gen_batch_builder(self, n, length, temp, cfg=None):
        """Jitted (params, backbones (R,P,16), keys (R,2)) -> per-row
        samples ((R,n,L), (R,n)). vmap over rows with per-row PRNG keys:
        each row samples exactly as it would alone, so fused batches are
        reproducible per pipeline."""
        cfg = cfg or self.gen_cfg

        def row(params, bb, key):
            s, lp = prot.progen_sample(params, bb[None], n=n, length=length,
                                       cfg=cfg, key=key, temperature=temp)
            return s[0], lp[0]

        return jax.jit(jax.vmap(row, in_axes=(None, 0, 0)))

    def _gen_batch_builder_masked(self, n, length, temp, cfg=None):
        """Masked variant: every row samples at the shared bucketed
        ``length``; a per-row ``row_len`` (traced) masks the log-likelihood
        to the row's true length, and the host truncates the returned
        tokens. A row's stream depends only on (seed, bucket) — never on
        which other rows share the batch — so mixed-length fusion stays
        deterministic per pipeline."""
        cfg = cfg or self.gen_cfg

        def row(params, bb, key, row_len):
            s, tok_lps = prot.progen_sample(
                params, bb[None], n=n, length=length, cfg=cfg, key=key,
                temperature=temp, return_token_lps=True)
            valid = (jnp.arange(length)[None, :]
                     < row_len).astype(tok_lps.dtype)
            return s[0], (tok_lps[0] * valid).sum(-1)

        return jax.jit(jax.vmap(row, in_axes=(None, 0, 0, 0)))

    def generate_batch(self, submesh, payload):
        """Sample a (rows, n, L) candidate stack in one jitted call per
        device — one row per pipeline.

        payload: backbones (R, P, 16) f32 (or (P, 16) for one row); seeds
        (R,) per-row PRNG seeds; n, length, temperature as in ``generate``.
        The row dim is padded up to a ``BATCH_BUCKETS`` size (pad rows
        repeat the last real row, are dropped before returning, and cannot
        perturb real rows — every row samples from its own key) and the
        padded stack splits evenly across the sub-mesh's devices.

        Masked mixed-length form: with per-row ``row_lens``, ``length`` is
        the shared bucketed sample length — every row samples at the bucket
        (per-row keys keep streams batch-composition-independent), the
        log-likelihood is masked to the row's true length on device, and
        the returned tokens are truncated per row host-side.

        Returns {"rows": [(seqs (n,L) i32, lls (n,) f32) per row],
        "batch": occupancy info (incl. ``len_occupancy``), "gen_version":
        generator version the dispatch sampled from}.

        Paged decode form (``payload["decode"] == "paged"``): routed to
        ``_generate_batch_paged`` — token-by-token continuous batching
        over a paged KV cache instead of per-row dense sampling.
        """
        if payload.get("decode") == "paged":
            return self._generate_batch_paged(submesh, payload)
        bbs = np.asarray(payload["backbones"], np.float32)
        if bbs.ndim == 2:
            bbs = bbs[None]
        R = bbs.shape[0]
        n, length = int(payload["n"]), int(payload["length"])
        temp = float(payload.get("temperature", 1.0))
        seeds = np.asarray(payload["seeds"], np.int64).reshape(-1)
        row_lens = payload.get("row_lens")
        masked = row_lens is not None
        if masked:
            row_lens = np.asarray(row_lens, np.int32).reshape(-1)
            len_occ = float(row_lens.sum()) / float(R * length)
            (bbs, seeds, row_lens), B = _pad_rows([bbs, seeds, row_lens], R)
        else:
            len_occ = 1.0
            (bbs, seeds), B = _pad_rows([bbs, seeds], R)
        # per-row threefry keys packed host-side ((hi, lo) uint32 words, the
        # layout jax.random.PRNGKey produces) — one vectorized construction
        # instead of B eager device calls
        s64 = seeds.astype(np.uint64)
        keys = np.stack([(s64 >> np.uint64(32)).astype(np.uint32),
                         (s64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)],
                        axis=1)
        ns, store, gcfg, sfx = self._gen_set(payload)
        bbs = bbs[:, :gcfg.frontend_seq]
        ver, gparams = store.current()  # whole-dispatch snapshot
        devices, per = _split_devices(submesh, B)
        ndev = len(devices)
        futures = []
        for i, dev in enumerate(devices):
            sl = slice(i * per, (i + 1) * per)
            gp = self._params_on(("gen", ns, ver), gparams, dev)
            b = jax.device_put(bbs[sl], dev)
            k = jax.device_put(keys[sl], dev)
            if masked:
                fn = self._compiled(
                    f"generate_mb{per}_n{n}_L{length}_t{temp}{sfx}", dev,
                    lambda: self._gen_batch_builder_masked(n, length, temp,
                                                           gcfg))
                futures.append(fn(gp, b, k,
                                  jax.device_put(row_lens[sl], dev)))
            else:
                fn = self._compiled(
                    f"generate_b{per}_n{n}_L{length}_t{temp}{sfx}", dev,
                    lambda: self._gen_batch_builder(n, length, temp, gcfg))
                futures.append(fn(gp, b, k))
        seqs = np.concatenate([np.asarray(f[0]) for f in futures])[:R]
        lls = np.concatenate([np.asarray(f[1]) for f in futures])[:R]
        rows = [(seqs[r][:, :row_lens[r]].astype(np.int32) if masked
                 else seqs[r].astype(np.int32),
                 lls[r].astype(np.float32)) for r in range(R)]
        batch = {"rows": R, "bucket": B, "occupancy": R / B, "devices": ndev,
                 "len_occupancy": len_occ}
        gen_batch_log.append(batch)
        return {"rows": rows, "batch": dict(batch), "gen_version": ver}

    def _backbone_batch_builder(self, m, sigma):
        """Jitted (bases (R,P,16), targets (R,16), keys (R,2)) -> per-row
        ((R,m,P,16) perturbed backbones, (R,m) target-fit scores). vmap
        over rows with per-row PRNG keys, like ``_gen_batch_builder`` —
        a row's candidates depend only on its own (base, seed), so fused
        backbone batches are reproducible per pipeline."""
        def row(base, tgt, key):
            noise = jax.random.normal(key, (m,) + base.shape, base.dtype)
            cands = base[None] + sigma * noise
            emb = cands.mean(axis=1)            # (m, 16) pooled embedding
            scores = -((emb - tgt[None]) ** 2).mean(axis=-1)
            return cands, scores

        return jax.jit(jax.vmap(row, in_axes=(0, 0, 0)))

    def backbone_batch(self, submesh, payload):
        """Backbone-sampling stage: perturb each row's base backbone into
        ``m`` candidates and score their pooled-embedding fit against the
        row's target — the cheap, wide first stage of a staged binder
        pipeline (an RFdiffusion analogue at toy scale: many structures
        proposed per call, the best carried forward).

        payload: bases (R, P, 16) f32 (or (P, 16) for one row); targets
        (R, 16) f32 (or (16,) shared); seeds (R,) per-row PRNG seeds;
        m int; sigma float perturbation scale. Rows pad to a
        ``BATCH_BUCKETS`` size and split across the sub-mesh like the
        other batched kinds.

        Returns {"rows": [(cands (m,P,16) f32, scores (m,) f32) per row],
        "batch": occupancy info}."""
        bases = np.asarray(payload["bases"], np.float32)
        if bases.ndim == 2:
            bases = bases[None]
        R = bases.shape[0]
        tgts = np.asarray(payload["targets"], np.float32)
        if tgts.ndim == 1:
            tgts = np.tile(tgts[None], (R, 1))
        seeds = np.asarray(payload["seeds"], np.int64).reshape(-1)
        m = int(payload["m"])
        sigma = float(payload.get("sigma", 0.1))
        (bases, tgts, seeds), B = _pad_rows([bases, tgts, seeds], R)
        s64 = seeds.astype(np.uint64)
        keys = np.stack([(s64 >> np.uint64(32)).astype(np.uint32),
                         (s64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)],
                        axis=1)
        P = bases.shape[1]
        devices, per = _split_devices(submesh, B)
        futures = []
        for i, dev in enumerate(devices):
            sl = slice(i * per, (i + 1) * per)
            fn = self._compiled(
                f"backbone_b{per}_m{m}_P{P}_s{sigma}", dev,
                lambda: self._backbone_batch_builder(m, sigma))
            futures.append(fn(jax.device_put(bases[sl], dev),
                              jax.device_put(tgts[sl], dev),
                              jax.device_put(keys[sl], dev)))
        cands = np.concatenate([np.asarray(f[0]) for f in futures])[:R]
        scores = np.concatenate([np.asarray(f[1]) for f in futures])[:R]
        rows = [(cands[r].astype(np.float32), scores[r].astype(np.float32))
                for r in range(R)]
        batch = {"rows": R, "bucket": B, "occupancy": R / B,
                 "devices": len(devices)}
        backbone_log.append(batch)
        return {"rows": rows, "batch": dict(batch)}

    def _paged_parse(self, payload, length, gcfg=None):
        """Normalize a paged generate payload's per-row arrays."""
        bbs = np.asarray(payload["backbones"], np.float32)
        if bbs.ndim == 2:
            bbs = bbs[None]
        bbs = bbs[:, :(gcfg or self.gen_cfg).frontend_seq]
        seeds = np.asarray(payload["seeds"], np.int64).reshape(-1)
        rl = payload.get("row_lens")
        rl = (np.asarray(rl, np.int32).reshape(-1) if rl is not None
              else np.full(bbs.shape[0], length, np.int32))
        return bbs, seeds, rl

    def _generate_batch_paged(self, submesh, payload):
        """Continuously-batched sampling over a paged KV cache.

        Every (row, candidate) pair becomes one decode slot in a
        ``PagedDecodeEngine`` compiled once per (slots, length bucket,
        page size) on the sub-mesh's first device. Candidate ``c`` of a
        row seeded ``s`` samples from ``fold_in(PRNGKey(s), c)`` — streams
        are composition-independent, so a row's tokens are identical
        whether it decodes alone or shares the engine with other rows.

        Live admission: when the executor injected an ``AdmissionPort``
        (``payload["_admit"]``, rule ``live=True``), the engine's poll
        hook pulls compatible queued tasks into the *running* decode loop
        whenever slots free up — their rows join mid-flight with zero new
        compilations (the engine's jitted admit/step executables are shape
        stable) and their result rows follow the initial members' rows,
        matching the worker's member fan-out order.
        """
        dev = submesh.devices.flat[0]
        n = int(payload["n"])
        length = int(payload["length"])
        temp = float(payload.get("temperature", 1.0))
        page_size = int(payload.get("page_size", 8))
        port = payload.get("_admit")
        ns, store, gcfg, sfx = self._gen_set(payload)
        bbs, seeds, row_lens = self._paged_parse(payload, length, gcfg)
        R0 = bbs.shape[0]
        slots = int(payload.get("decode_slots", 0)) \
            or min(max(R0 * n, 4), 32)
        eng_kind = f"paged{slots}_L{length}_p{page_size}{sfx}"
        eng = self._compiled(
            eng_kind, dev,
            lambda: prot.PagedDecodeEngine(
                gcfg, slots=slots, max_new=length,
                page_size=page_size, device=dev))
        ver, gparams = store.current()
        gp = self._params_on(("gen", ns, ver), gparams, dev)

        records = []           # (tag0, n_rows) in result-row order

        def specs_for(bb, sds, rl, tag0):
            out = []
            for r in range(bb.shape[0]):
                ckeys = _fold_in_keys(sds[r], n)
                out += [dict(backbone=bb[r], key=ckeys[c],
                             length=int(rl[r]), tag=(tag0, r, c))
                        for c in range(n)]
            records.append((tag0, bb.shape[0]))
            return out

        admitted = []
        occ_rows = [(int(row_lens.sum()), R0)]

        def poll(free):
            if port is None or free < n:
                return []
            out = []
            for t in port.take(free // n):
                admitted.append(t)
                abb, asd, arl = self._paged_parse(t.payload, length, gcfg)
                out += specs_for(abb, asd, arl, len(admitted))
                occ_rows.append((int(arl.sum()), abb.shape[0]))
            return out

        with eng.lock:
            steps0, slot_steps0 = eng.steps, eng.slot_steps
            page_steps0 = eng.page_steps
            step_s0 = eng.step_host_s
            try:
                res = eng.run(gp, temp,
                              specs=specs_for(bbs, seeds, row_lens, 0),
                              poll=poll)
            except Exception:
                # a failed run leaves the engine's slots and donated device
                # state half-updated: drop it so a retry builds a fresh one
                with self._cache_lock:
                    self._cache.pop((eng_kind, dev.id), None)
                raise
        rows = []
        for tag0, nr in sorted(records):
            for r in range(nr):
                picks = [res[(tag0, r, c)] for c in range(n)]
                rows.append((np.stack([p[0] for p in picks]).astype(np.int32),
                             np.asarray([p[1] for p in picks], np.float32)))
        R = sum(nr for _, nr in records)
        tok_sum = sum(s for s, _ in occ_rows)
        batch = {"rows": R, "bucket": slots,
                 "occupancy": min(1.0, (R * n) / slots), "devices": 1,
                 "len_occupancy": tok_sum / float(R * length),
                 "decode": "paged", "admitted": len(admitted),
                 "steps": eng.steps - steps0,
                 "slot_steps": eng.slot_steps - slot_steps0,
                 "page_steps": eng.page_steps - page_steps0,
                 "step_host_s": eng.step_host_s - step_s0}
        gen_batch_log.append(batch)
        return {"rows": rows, "batch": dict(batch), "gen_version": ver}

    def register_all(self, executor, generate_batch_rows: int = None,
                     coalesce: bool = True, length_buckets=None,
                     decode_kernel: bool = False):
        """Register every task fn (and, when the executor supports it, the
        batched kinds' coalesce rules). ``generate_batch_rows`` bounds the
        fused generate batch — pass ``ProtocolConfig.generate_batch_size``
        so the config's 'up to this many rows per device batch' contract
        holds; None keeps the BATCH_BUCKETS cap. ``coalesce=False`` skips
        the coalesce rules (benchmark baselines register their own).
        ``length_buckets`` installs campaign-derived token-dim bucket edges
        (masked payload padding + masked coalesce keys); None keeps the
        payload's current table (global ``LENGTH_BUCKETS`` by default).
        ``decode_kernel=True`` marks the generate_batch rule ``live`` so
        paged dispatches can admit queued tasks mid-decode."""
        if length_buckets is not None:
            self.length_buckets = tuple(length_buckets)
        executor.register("generate", self.generate)
        executor.register("generate_batch", self.generate_batch)
        executor.register("predict", self.predict)
        executor.register("predict_batch", self.predict_batch)
        executor.register("backbone_batch", self.backbone_batch)
        if coalesce and hasattr(executor, "register_coalescable"):
            executor.register_coalescable(
                "predict_batch",
                predict_batch_coalesce_rule(
                    length_buckets=self.length_buckets))
            executor.register_coalescable(
                "generate_batch",
                generate_batch_coalesce_rule(
                    max_rows=(generate_batch_rows if generate_batch_rows
                              else BATCH_BUCKETS[-1]),
                    prefix_len=self.gen_cfg.frontend_seq,
                    live=decode_kernel))
            executor.register_coalescable(
                "backbone_batch", backbone_batch_coalesce_rule())

    def coalesce_rule_for(self, kind: str, *, max_rows: int = None,
                          admission_window: float = None):
        """Build the coalesce rule for one of this payload's batched task
        kinds with per-stage overrides — how ``register_stages`` turns a
        ``StageSpec``'s coalesce knobs into a registered rule."""
        kw = {}
        if max_rows is not None:
            kw["max_rows"] = int(max_rows)
        if kind == "predict_batch":
            return predict_batch_coalesce_rule(
                length_buckets=self.length_buckets, **kw)
        if kind == "generate_batch":
            if admission_window is not None:
                kw["admission_window"] = float(admission_window)
            return generate_batch_coalesce_rule(
                prefix_len=self.gen_cfg.frontend_seq, **kw)
        if kind == "backbone_batch":
            if admission_window is not None:
                kw["admission_window"] = float(admission_window)
            return backbone_batch_coalesce_rule(**kw)
        raise KeyError(f"no coalesce rule for task kind {kind!r}")

    def register_stages(self, executor, stages, coalesce: bool = True):
        """Wire a stage table (``core.stages.StageSpec`` sequence) into the
        executor: create each stage's param-set namespace (generator for
        sampling kinds, scorer for fold kinds) and register its
        stage-specific coalesce rule (keyed ``(kind, stage)`` — the
        executor already keeps cross-stage tasks apart). Call after
        ``register_all``; safe to call once per protocol sharing stages.
        ``coalesce=False`` creates the namespaces but skips the rules, so
        an unfused baseline campaign still resolves its param sets."""
        for s in stages:
            if s.params != "default":
                if s.kind in ("generate", "generate_batch"):
                    self.add_generator(s.params)
                elif s.kind in ("predict", "predict_batch"):
                    self.add_scorer(s.params)
            if s.kind in ("predict", "generate"):  # solo kinds never fuse
                continue
            if coalesce and hasattr(executor, "register_coalescable"):
                executor.register_coalescable(
                    s.kind,
                    self.coalesce_rule_for(
                        s.kind, max_rows=s.max_rows,
                        admission_window=s.admission_window),
                    stage=s.name)


def predict_batch_coalesce_rule(max_rows: int = BATCH_BUCKETS[-1],
                                length_buckets=None):
    """Coalescing contract for ``predict_batch`` tasks.

    Legacy payloads (no ``seq_lens``) fuse on the exact (sequence length,
    chain split) — bit-for-bit the seed behavior. Masked payloads fuse on
    the *length bucket* alone: tasks of different sequence lengths and
    different receptor splits merge into one dense padded batch (per-row
    ``seq_lens``/``chain_splits`` threaded through), which is what keeps a
    mixed-receptor-length campaign from degenerating to 1-row dispatches.
    The two families never fuse with each other, so adding masked tasks to
    a campaign cannot perturb legacy results."""
    from repro.runtime.executor import CoalesceRule

    def n_rows(task):
        s = np.asarray(task.payload["sequences"])
        return 1 if s.ndim == 1 else int(s.shape[0])

    def width(task):
        return int(np.asarray(task.payload["sequences"]).shape[-1])

    def key(task):
        ns = task.payload.get("params")  # param-set namespace: tasks
        # scoring with different fold param sets must never share a batch
        if "seq_lens" in task.payload:
            return ("masked", bucket_len(width(task), length_buckets), ns)
        return (width(task), int(task.payload["receptor_len"]), ns)

    def merge(tasks):
        masked = "seq_lens" in tasks[0].payload
        Lb = (bucket_len(max(width(t) for t in tasks), length_buckets)
              if masked else None)
        seq_stacks, tgt_stacks, lens, splits = [], [], [], []
        for t in tasks:
            s = np.asarray(t.payload["sequences"], np.int32)
            if s.ndim == 1:
                s = s[None]
            g = np.asarray(t.payload["target"], np.float32)
            if g.ndim == 1:
                g = np.tile(g[None], (s.shape[0], 1))
            if masked:
                if Lb > s.shape[1]:   # pad member stacks to the bucket
                    s = np.concatenate(
                        [s, np.zeros((s.shape[0], Lb - s.shape[1]),
                                     np.int32)], axis=1)
                lens.append(np.asarray(t.payload["seq_lens"],
                                       np.int32).reshape(-1))
                splits.append(np.asarray(
                    t.payload.get("chain_splits",
                                  np.full(s.shape[0],
                                          int(t.payload["receptor_len"]))),
                    np.int32).reshape(-1))
            seq_stacks.append(s)
            tgt_stacks.append(g)
        fused = {"sequences": np.concatenate(seq_stacks),
                 "target": np.concatenate(tgt_stacks),
                 "receptor_len": tasks[0].payload["receptor_len"]}
        if masked:
            fused["seq_lens"] = np.concatenate(lens)
            fused["chain_splits"] = np.concatenate(splits)
        if tasks[0].payload.get("params"):
            fused["params"] = tasks[0].payload["params"]
        return fused

    def split(tasks, result):
        return _fan_out_rows(tasks, result, n_rows)

    return CoalesceRule(key=key, merge=merge, split=split, rows=n_rows,
                        max_rows=max_rows)


def generate_batch_coalesce_rule(max_rows: int = BATCH_BUCKETS[-1],
                                 admission_window: float = 0.005,
                                 prefix_len: int = None,
                                 live: bool = False):
    """Coalescing contract for ``generate_batch`` tasks: one-row tasks from
    *different* pipelines with the same (n, length, backbone prefix shape,
    temperature) stack into one device batch; per-row seeds keep each
    pipeline's sampling stream. The default ``admission_window`` enables
    rolling admission — compatible tasks queued while a batch is being
    assembled join it instead of waiting a full cycle.

    Masked payloads (per-row ``row_lens``, ``length`` already bucketed by
    the protocol) additionally fuse across *backbone lengths*: backbones
    are compared and merged on their ``prefix_len`` prefix (all the model
    consumes), so pipelines for different-size receptors share one device
    batch. Masked and legacy tasks never fuse with each other, and paged
    tasks (``decode == "paged"``) only fuse with paged ones — the decode
    mode is part of the compatibility key. ``live=True`` lets the paged
    payload pull compatible queued tasks into a *running* decode loop via
    the executor's ``AdmissionPort`` (inert for the dense path, which
    never polls the port)."""
    from repro.runtime.executor import CoalesceRule

    def bbs(task):
        b = np.asarray(task.payload["backbones"], np.float32)
        return b[None] if b.ndim == 2 else b

    def n_rows(task):
        return int(bbs(task).shape[0])

    def key(task):
        p = task.payload
        shape = bbs(task).shape[1:]
        decode = p.get("decode")
        ns = p.get("params")   # param-set namespace never fuses across
        if "row_lens" in p or decode == "paged":
            if prefix_len:
                shape = (min(shape[0], prefix_len),) + shape[1:]
            return ("masked", decode, int(p["n"]), int(p["length"]), shape,
                    float(p.get("temperature", 1.0)), ns)
        return (int(p["n"]), int(p["length"]), shape,
                float(p.get("temperature", 1.0)), ns)

    def merge(tasks):
        p0 = tasks[0].payload
        masked = "row_lens" in p0 or p0.get("decode") == "paged"
        stacks = [bbs(t) for t in tasks]
        if masked and prefix_len:
            stacks = [b[:, :prefix_len] for b in stacks]
        fused = {"backbones": np.concatenate(stacks),
                 "seeds": np.concatenate(
                     [np.asarray(t.payload["seeds"], np.int64).reshape(-1)
                      for t in tasks]),
                 "n": p0["n"],
                 "length": p0["length"],
                 "temperature": p0.get("temperature", 1.0)}
        if masked:
            fused["row_lens"] = np.concatenate(
                [np.asarray(t.payload.get(
                     "row_lens", np.full(bbs(t).shape[0], int(p0["length"]),
                                         np.int32)), np.int32).reshape(-1)
                 for t in tasks])
        for k in ("decode", "decode_slots", "page_size", "params"):
            if k in p0:
                fused[k] = p0[k]
        return fused

    def split(tasks, result):
        return _fan_out_rows(tasks, result, n_rows)

    return CoalesceRule(key=key, merge=merge, split=split, rows=n_rows,
                        max_rows=max_rows,
                        admission_window=admission_window, live=live)


def backbone_batch_coalesce_rule(max_rows: int = BATCH_BUCKETS[-1],
                                 admission_window: float = 0.005):
    """Coalescing contract for ``backbone_batch`` tasks: one-row tasks
    from different pipelines with the same (m, base shape, sigma) stack
    into one device batch; per-row seeds keep each pipeline's candidate
    stream, so fused backbone sampling is composition-independent exactly
    like ``generate_batch``."""
    from repro.runtime.executor import CoalesceRule

    def bases(task):
        b = np.asarray(task.payload["bases"], np.float32)
        return b[None] if b.ndim == 2 else b

    def n_rows(task):
        return int(bases(task).shape[0])

    def key(task):
        p = task.payload
        return (int(p["m"]), bases(task).shape[1:],
                float(p.get("sigma", 0.1)), p.get("params"))

    def merge(tasks):
        p0 = tasks[0].payload

        def tgts(t):
            g = np.asarray(t.payload["targets"], np.float32)
            return np.tile(g[None], (bases(t).shape[0], 1)) \
                if g.ndim == 1 else g

        fused = {"bases": np.concatenate([bases(t) for t in tasks]),
                 "targets": np.concatenate([tgts(t) for t in tasks]),
                 "seeds": np.concatenate(
                     [np.asarray(t.payload["seeds"], np.int64).reshape(-1)
                      for t in tasks]),
                 "m": p0["m"], "sigma": p0.get("sigma", 0.1)}
        if p0.get("params"):
            fused["params"] = p0["params"]
        return fused

    def split(tasks, result):
        return _fan_out_rows(tasks, result, n_rows)

    return CoalesceRule(key=key, merge=merge, split=split, rows=n_rows,
                        max_rows=max_rows,
                        admission_window=admission_window)


def clear_compile_log():
    for v in compile_log.values():
        v.clear()
    batch_log.clear()
    gen_batch_log.clear()
    backbone_log.clear()


class FinetunePayload:
    """The ``finetune`` task kind — the §V model-evolution trainer payload:
    accepted designs (HPC output) become training data that evolves the
    generative model, with a fitness-weighted NLL objective (the simplest
    form of the paper's MProt-DPO-flavoured 'evolve the generator').

    Built on ``optim.train_step.make_train_step``: one jitted data-parallel
    train step with the design batch sharded across the allocated sub-mesh's
    devices (params replicated; GSPMD inserts the gradient all-reduce)
    instead of looping a single-device jitted step. Evolved params are
    published to the generator's ``ParamStore`` as a new version —
    generators hot-swap on their next dispatch, in-flight dispatches finish
    on the version they started with.

    Preemption contract: for preemptible tasks the executor injects the
    live task as ``payload["_task"]``; between train steps the loop checks
    ``preempt_requested`` and yields early, returning host-side resume
    state (params/opt-state/step) in the result. The trainer service
    resubmits the continuation (``payload["resume"]``) on the next idle
    window, so a queued design task waits at most one train step and no
    training progress is lost.
    """

    def __init__(self, protein_payload, lr=1e-4, steps=20, param_store=None):
        from repro.optim import OptConfig
        self.pp = protein_payload
        self.store = param_store or protein_payload.param_store
        self.opt = OptConfig(lr=lr, warmup_steps=2, total_steps=steps,
                             weight_decay=0.0)
        self.steps = steps
        self._step_fn = None

    def _train_step(self):
        """Jitted data-parallel train step (built once; XLA recompiles per
        new batch shape / sub-mesh shape)."""
        if self._step_fn is None:
            from repro.optim import make_train_step
            cfg = self.pp.gen_cfg

            def loss_fn(params, batch):
                # optional per-row lengths mask a mixed-length design batch
                # (rows padded to a common width) — absent for the
                # homogeneous batches ReplayBuffer.sample produces today
                lp = prot.progen_logprobs(params, batch["backbones"],
                                          batch["sequences"], cfg,
                                          seq_lens=batch.get("seq_lens"))
                w = batch["weights"]
                wn = w / jnp.maximum(w.sum(), 1e-6)
                loss = -(wn * lp).sum()
                real = (w > 0).astype(jnp.float32)   # pad rows weigh 0
                mean_ll = (real * lp).sum() / jnp.maximum(real.sum(), 1.0)
                return loss, {"loss": loss, "mean_ll": mean_ll}

            self._step_fn = jax.jit(
                make_train_step(cfg, self.opt, loss_fn=loss_fn))
        return self._step_fn

    def finetune(self, submesh, payload):
        """payload: backbones (B,P,16) f32; sequences (B,L) i32; weights
        (B,) f32 (fitness-derived, >= 0); steps (optional int); resume
        (optional, from a preempted run's result); _task (injected by the
        executor for preemptible tasks).

        Returns metrics incl. base/new generator version, or — when
        preempted — partial metrics plus ``resume`` state."""
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.optim import init_opt_state
        t_start = time.monotonic()
        task = payload.get("_task")
        cfg = self.pp.gen_cfg
        mesh = submesh.mesh
        ndev = submesh.n_devices
        seqs = np.asarray(payload["sequences"], np.int32)
        bbs = np.asarray(payload["backbones"],
                         np.float32)[:, :cfg.frontend_seq]
        w = np.maximum(np.asarray(payload["weights"], np.float32), 0.0)
        n_real = int(seqs.shape[0])
        pad = (-n_real) % ndev   # data-parallel split needs B % ndev == 0
        if pad:
            seqs = np.concatenate([seqs, np.repeat(seqs[-1:], pad, 0)])
            bbs = np.concatenate([bbs, np.repeat(bbs[-1:], pad, 0)])
            w = np.concatenate([w, np.zeros(pad, np.float32)])
        repl = NamedSharding(mesh, PartitionSpec())
        rows = NamedSharding(mesh, PartitionSpec(tuple(mesh.axis_names)))
        resume = payload.get("resume")
        if resume is not None:
            base_version = int(resume["base_version"])
            params, opt_state = resume["params"], resume["opt_state"]
            start = int(resume["step"])
            losses = list(resume["losses"])
            mean_lls = list(resume["mean_lls"])
        else:
            base_version, params = self.store.current()
            opt_state = init_opt_state(params, self.opt)
            start, losses, mean_lls = 0, [], []
        total = int(payload.get("steps", self.steps))
        params = jax.device_put(params, repl)
        opt_state = jax.device_put(opt_state, repl)
        batch = {"backbones": jax.device_put(bbs, rows),
                 "sequences": jax.device_put(seqs, rows),
                 "weights": jax.device_put(w, rows)}
        if payload.get("seq_lens") is not None:
            sl = np.asarray(payload["seq_lens"], np.int32).reshape(-1)
            if pad:
                sl = np.concatenate([sl, np.repeat(sl[-1:], pad)])
            batch["seq_lens"] = jax.device_put(sl, rows)
        step = self._train_step()
        preempted = False
        k = start
        while k < total:
            params, opt_state, metrics = step(params, opt_state, batch)
            losses.append(float(metrics["loss"]))
            mean_lls.append(float(metrics["mean_ll"]))
            k += 1
            if task is not None and k < total \
                    and (task.preempt_requested or task.canceled):
                preempted = True   # yield the sub-mesh to design work
                break
        info = {"steps_done": k, "steps_run": k - start,
                "n_designs": n_real, "n_devices": ndev,
                "base_version": base_version,
                "elapsed_s": time.monotonic() - t_start}
        if preempted:
            return dict(info, preempted=True, resume={
                "params": jax.device_get(params),
                "opt_state": jax.device_get(opt_state),
                "step": k, "base_version": base_version,
                "losses": losses, "mean_lls": mean_lls})
        # publish the evolved generator as a new version; generators
        # hot-swap on their next dispatch
        new_version = self.store.publish(jax.device_get(params))
        return dict(info, preempted=False, new_version=new_version,
                    loss_first=losses[0], loss_last=losses[-1],
                    mean_ll_first=mean_lls[0], mean_ll_last=mean_lls[-1])

    def register(self, executor):
        executor.register("finetune", self.finetune)
