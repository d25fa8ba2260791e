"""Protein payload models for the IMPRESS protocol.

ProGen — ProteinMPNN analogue: a structure-conditioned sequence model.
  The backbone structure is encoded as a fixed-length prefix of structure
  embeddings (the role ProteinMPNN's graph encoder plays); the decoder
  autoregressively emits amino-acid tokens. ``sample`` returns N candidate
  sequences and their log-likelihoods (Stage 1+2 of the pipeline).

FoldScore — AlphaFold analogue: predicts structure-confidence metrics for a
  (sequence, target) complex: per-residue pLDDT in [0,100], pTM in [0,1] and
  an inter-chain pAE matrix in [0,30]. A *fixed randomly-initialized*
  FoldScore is a deterministic smooth function of the sequence — the
  synthetic fitness landscape the genetic protocol hill-climbs, playing the
  role AlphaFold's confidence heads play in the paper (DESIGN.md §5).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import lm as lm_mod
from repro.models.common import dense_init, split_keys


class FoldMetrics(NamedTuple):
    plddt: jax.Array   # (B,) mean per-residue pLDDT, 0..100 (higher better)
    ptm: jax.Array     # (B,) 0..1 (higher better)
    pae: jax.Array     # (B,) inter-chain mean pAE, 0..30 (lower better)


def metrics_rows(m: FoldMetrics, n: int | None = None) -> list:
    """Materialize batched FoldMetrics as one host-side dict per row (the
    per-candidate contract of the protocol layer). ``n`` truncates padded
    bucket rows. One ``.tolist()`` per metric instead of 3×N scalar
    ``float(arr[i])`` reads — the indexing form is measurable host-side
    overhead at bucket 64."""
    plddt = np.asarray(m.plddt, np.float32).tolist()
    ptm = np.asarray(m.ptm, np.float32).tolist()
    pae = np.asarray(m.pae, np.float32).tolist()
    n = len(plddt) if n is None else n
    return [{"plddt": pl, "ptm": pt, "pae": pa}
            for pl, pt, pa in zip(plddt[:n], ptm[:n], pae[:n])]


# ---------------------------------------------------------------------------
# ProGen
# ---------------------------------------------------------------------------


def init_progen(key, cfg):
    k1, k2 = jax.random.split(key)
    params = lm_mod.init_lm(k1, cfg)
    # structure encoder stub: projects backbone features (B, P, 16) to d
    params["struct_proj"] = {
        "w": dense_init(k2, (16, cfg.d_model), 16, jnp.float32)}
    return params


def encode_structure(params, backbone, cfg):
    """backbone (B, P, 16) coarse features -> prefix embeddings (B,P,d)."""
    return jnp.einsum("bpf,fd->bpd", backbone.astype(jnp.float32),
                      params["struct_proj"]["w"]).astype(
                          jnp.dtype(cfg.compute_dtype))


def progen_logprobs(params, backbone, seqs, cfg, seq_lens=None):
    """Log-likelihood of sequences (B, L) given structure (B, P, 16).

    ``seq_lens`` (B,) i32 masks per-row padding: positions >= a row's true
    length contribute nothing to its sum. The decoder is causal, so a
    padded row's valid-position log-probs are identical to scoring the row
    alone at its true length — masking makes mixed-length rows safe to fuse
    into one dense batch. None keeps the seed full-width sum."""
    patches = encode_structure(params, backbone, cfg)
    inputs = jnp.concatenate(
        [jnp.zeros((seqs.shape[0], 1), seqs.dtype), seqs[:, :-1]], axis=1)
    logits, _ = lm_mod.lm_logits(
        params, {"inputs": inputs, "targets": seqs, "patches": patches}, cfg)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    tok_lp = jnp.take_along_axis(logp, seqs[..., None], axis=-1)[..., 0]
    if seq_lens is None:
        return tok_lp.sum(-1)
    valid = (jnp.arange(seqs.shape[1])[None, :]
             < seq_lens[:, None]).astype(tok_lp.dtype)
    return (tok_lp * valid).sum(-1)


def progen_sample(params, backbone, n, length, cfg, key, temperature=1.0,
                  return_token_lps=False):
    """Sample n sequences per structure. backbone (B,P,16).
    Returns (seqs (B,n,L) i32, loglik (B,n)) — or, with
    ``return_token_lps``, (seqs, per-token log-probs (B,n,L)) so callers
    can re-aggregate likelihoods under a per-row length mask (the
    length-bucketed sampling path). The sampled tokens are identical either
    way; the legacy summed loglik is untouched."""
    B = backbone.shape[0]
    bb = jnp.repeat(backbone, n, axis=0)                       # (B*n,P,16)
    patches = encode_structure(params, bb, cfg)
    key, k0 = jax.random.split(key)

    def step(carry, k):
        caches, tok, t, lp = carry
        logits, caches = lm_mod.decode_step(params, caches, tok, t, cfg)
        logits = logits.astype(jnp.float32)
        logits = logits.at[:, cfg.vocab_size:].set(-1e30)  # mask pad vocab
        nxt = jax.random.categorical(k, logits / temperature, axis=-1)
        step_lp = jnp.take_along_axis(
            jax.nn.log_softmax(logits, -1), nxt[:, None], -1)[:, 0]
        return (caches, nxt[:, None], t + 1, lp + step_lp), (nxt, step_lp)

    bos = jnp.zeros((B * n, 1), jnp.int32)
    logits, caches, t0 = lm_mod.prefill(
        params, {"inputs": bos, "patches": patches}, cfg,
        cache_len=cfg.frontend_seq + 1 + length)
    logits = logits.astype(jnp.float32).at[:, cfg.vocab_size:].set(-1e30)
    first = jax.random.categorical(k0, logits / temperature, axis=-1)
    lp0 = jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                              first[:, None], -1)[:, 0]
    keys = jax.random.split(key, length - 1)
    (caches, _, _, lp), (toks, step_lps) = jax.lax.scan(
        step, (caches, first[:, None], t0, lp0), keys)
    seqs = jnp.concatenate([first[None], toks], axis=0).T       # (B*n, L)
    if return_token_lps:
        tok_lps = jnp.concatenate([lp0[None], step_lps], axis=0).T  # (B*n,L)
        return seqs.reshape(B, n, length), tok_lps.reshape(B, n, length)
    return seqs.reshape(B, n, length), lp.reshape(B, n)


# ---------------------------------------------------------------------------
# Paged continuous-batching decode engine
# ---------------------------------------------------------------------------


class PagedDecodeEngine:
    """Continuous-batching ProGen sampler over a paged KV cache.

    A fixed number of decode *slots* share one pool of fixed-size K/V
    pages (``lm.init_paged_caches``). Every per-slot array (block tables,
    true lengths, end lengths, base keys, current and sampled tokens,
    log-likelihoods) lives in ``state`` on the device, donated to and
    rebound from each jitted call. Admission prefils one row's prompt into
    freshly popped pages (a fixed (1, S0) executable), samples its first
    token and writes the slot's row of that state; every step advances all
    active slots through one fused ``lm.paged_decode_step`` and, on the
    device, retires a slot that reached its end length (true length 0,
    block-table row to the trash page). A step takes nothing from the
    host. The host keeps numpy mirrors (``block_tables``, ``true_lens``,
    ``base_keys``, ``_slot_meta``) updated by the same arithmetic, never
    read back: they decide pages, counts and retirement, whose single
    read of the finished rows out of ``state`` returns their pages to a
    LIFO free pool — so rows of different lengths enter and leave a
    *running* batch without any shape change. ``trace_counts``
    increments only when a jitted body is (re)traced: a warm engine
    admitting/retiring rows must keep it constant (the zero-recompile
    probe the tests assert). ``steps`` and ``slot_steps`` count decode
    steps and, summed over them, the slots active in each;
    ``step_host_s`` sums the host wall time spent in ``step``. ``step``
    and ``_admit`` run inside the ``impress.paged.step`` /
    ``impress.paged.admit`` program spans.

    Sampling streams are composition-independent: row token ``i`` is
    drawn with ``fold_in(base_key, i)`` where ``base_key`` rides in with
    the spec — never from batch-level split order — and the batch shape
    is constant, so a row's tokens are bit-identical whether it decodes
    alone or joins mid-flight (tests/test_paged_decode.py).
    """

    def __init__(self, cfg, *, slots, max_new, page_size=8, device=None,
                 interpret=None):
        from collections import deque
        self.cfg = cfg
        self.slots = int(slots)
        self.max_new = int(max_new)
        self.page_size = int(page_size)
        self.prompt_len = cfg.frontend_seq + 1          # patches + BOS
        self.pages_per_row = -(-(self.prompt_len + self.max_new - 1)
                               // self.page_size)
        self.n_pages = self.slots * self.pages_per_row
        self.trash_page = self.n_pages                  # reserved page id
        self.device = device
        self.interpret = interpret
        self.lock = threading.Lock()                    # one run at a time
        # device_put of a numpy array can be zero-copy on CPU, so the
        # async-dispatched computation would alias host buffers we mutate
        # in place (the mirrors below) — always hand jax a copy.
        self._put = lambda x: jax.device_put(
            jax.tree.map(lambda a: a.copy() if isinstance(a, np.ndarray)
                         else a, x), device)
        # host bookkeeping and mirrors of the device's per-slot state
        self.free_pages = list(range(self.n_pages))     # LIFO pool
        self.block_tables = np.full((self.slots, self.pages_per_row),
                                    self.trash_page, np.int32)
        self.true_lens = np.zeros(self.slots, np.int32)
        self.base_keys = np.zeros((self.slots, 2), np.uint32)
        self._slot_meta = [None] * self.slots
        self._pending = deque()
        self._results = {}
        self.alloc_log = []                             # (tag, page ids)
        self.trace_counts = {"admit": 0, "step": 0}
        self.steps = 0                                  # decode steps run
        self.slot_steps = 0                             # sum of active slots
        self.step_host_s = 0.0                          # host time in step()
        self._temp = (None, None)                       # (value, on device)
        # device state, donated to every admit/step: the update is
        # in-place on device instead of copying the whole page pool — the
        # copies would grow with slots and dominate the step at wide
        # batches. The engine always rebinds self.state from the outputs,
        # so the consumed buffers are never read.
        self.state = self._put({
            "caches": lm_mod.init_paged_caches(cfg, self.n_pages + 1,
                                               self.page_size),
            "cur_tok": np.zeros((self.slots, 1), np.int32),
            "out_toks": np.zeros((self.slots, self.max_new), np.int32),
            "acc_lp": np.zeros(self.slots, np.float32),
            "block_tables": self.block_tables,
            "true_lens": self.true_lens,
            "end_lens": np.zeros(self.slots, np.int32),
            "base_keys": self.base_keys})
        self._admit_fn = jax.jit(self._build_admit(), donate_argnums=(7,))
        self._step_fn = jax.jit(self._build_step(), donate_argnums=(1,))

    # -- jitted bodies ---------------------------------------------------

    def _build_admit(self):
        cfg, S0, trash = self.cfg, self.prompt_len, self.trash_page

        def fn(params, backbone, bt_row, slot, base_key, end_len, temp,
               state):
            self.trace_counts["admit"] += 1     # traces only on compile
            patches = encode_structure(params, backbone, cfg)
            bos = jnp.zeros((1, 1), jnp.int32)
            logits, caches = lm_mod.paged_prefill(
                params, {"inputs": bos, "patches": patches}, cfg,
                state["caches"], bt_row[None])
            logits = logits.astype(jnp.float32).at[:, cfg.vocab_size:].set(
                -1e30)
            k0 = jax.random.fold_in(base_key, 0)
            tok0 = jax.random.categorical(k0, logits / temp, axis=-1)
            lp0 = jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                      tok0[:, None], -1)[0, 0]
            row = jnp.zeros((state["out_toks"].shape[1],), jnp.int32).at[
                0].set(tok0[0])
            live = end_len > S0         # a one-token row retires at once
            return dict(
                caches=caches,
                cur_tok=state["cur_tok"].at[slot, 0].set(tok0[0]),
                out_toks=state["out_toks"].at[slot].set(row),
                acc_lp=state["acc_lp"].at[slot].set(lp0),
                block_tables=state["block_tables"].at[slot].set(
                    jnp.where(live, bt_row, trash)),
                true_lens=state["true_lens"].at[slot].set(
                    jnp.where(live, S0, 0)),
                end_lens=state["end_lens"].at[slot].set(end_len),
                base_keys=state["base_keys"].at[slot].set(base_key))

        return fn

    def _build_step(self):
        cfg, S0, trash = self.cfg, self.prompt_len, self.trash_page
        interpret = self.interpret

        def fn(params, state, temp):
            self.trace_counts["step"] += 1      # traces only on compile
            true_lens, block_tables = state["true_lens"], state["block_tables"]
            out_toks = state["out_toks"]
            active = true_lens > 0
            lengths = jnp.where(active, true_lens + 1, 0)
            logits, caches = lm_mod.paged_decode_step(
                params, state["caches"], state["cur_tok"], true_lens,
                block_tables, lengths, cfg, interpret=interpret)
            logits = logits.astype(jnp.float32).at[:, cfg.vocab_size:].set(
                -1e30)
            idx = true_lens - S0 + 1            # tokens sampled so far
            keys = jax.vmap(jax.random.fold_in)(state["base_keys"], idx)
            nxt = jax.vmap(
                lambda k, lg: jax.random.categorical(k, lg / temp))(
                    keys, logits)
            step_lp = jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                          nxt[:, None], -1)[:, 0]
            rows = jnp.arange(nxt.shape[0])
            col = jnp.clip(idx, 0, out_toks.shape[1] - 1)
            keep = out_toks[rows, col]
            # a slot that reached its end length retires here, as the
            # host's mirror does: length 0 and the trash page, because an
            # inactive slot still writes K/V through its row, and a stale
            # row would write into pages another slot has taken
            done = active & (lengths >= state["end_lens"])
            return dict(
                state, caches=caches,
                cur_tok=jnp.where(active[:, None], nxt[:, None],
                                  state["cur_tok"]),
                out_toks=out_toks.at[rows, col].set(
                    jnp.where(active, nxt, keep)),
                acc_lp=state["acc_lp"] + jnp.where(active, step_lp, 0.0),
                true_lens=jnp.where(done, 0, lengths),
                block_tables=jnp.where(done[:, None], trash, block_tables))

        return fn

    # -- host-side lifecycle ---------------------------------------------

    def submit(self, *, backbone, key, length, tag):
        """Queue one row: backbone (frontend_seq, 16) f32, a (2,) uint32
        base PRNG key, the number of tokens to sample, and an opaque
        result tag. Admitted into the running batch as soon as a slot
        frees up."""
        length = int(length)
        if not 1 <= length <= self.max_new:
            raise ValueError(f"length {length} outside [1, {self.max_new}]")
        bb = np.asarray(backbone, np.float32)[:self.cfg.frontend_seq]
        self._pending.append({"backbone": bb,
                              "key": np.asarray(key, np.uint32).reshape(2),
                              "length": length, "tag": tag})

    def free_slots(self) -> int:
        return sum(m is None for m in self._slot_meta)

    def active_slots(self) -> int:
        return sum(m is not None for m in self._slot_meta)

    def _device_temp(self, temperature):
        """The sampling temperature as a device scalar, transferred only
        when its value changes: a run's steps and admits reuse one."""
        t = float(temperature)
        if self._temp[0] != t:
            self._temp = (t, self._put(np.float32(t)))
        return self._temp[1]

    def _admit(self, spec, params, temperature):
        with obs.span("paged.admit"):
            slot = self._slot_meta.index(None)
            need = -(-(self.prompt_len + spec["length"] - 1)
                     // self.page_size)
            pages = [self.free_pages.pop() for _ in range(need)]
            row = np.full(self.pages_per_row, self.trash_page, np.int32)
            row[:need] = pages
            self.block_tables[slot] = row
            self.base_keys[slot] = spec["key"]
            self.alloc_log.append((spec["tag"], tuple(pages)))
            self.state = self._admit_fn(
                params, self._put(spec["backbone"][None]), self._put(row),
                np.int32(slot), self._put(spec["key"]),
                np.int32(self.prompt_len + spec["length"] - 1),
                self._device_temp(temperature), self.state)
            self.true_lens[slot] = self.prompt_len
            self._slot_meta[slot] = {"tag": spec["tag"],
                                     "length": spec["length"], "done": 1}
            if spec["length"] <= 1:
                self._retire(slot)

    def _retire(self, slot, out_host=None, lp_host=None):
        """Free a finished row's pages and record its result; the device
        has already retired the slot. ``out_host`` / ``lp_host`` are
        optional host snapshots of out_toks / acc_lp so a step retiring
        many rows pays one device->host read, not 2/row."""
        meta = self._slot_meta[slot]
        if out_host is None:
            out_host = np.asarray(self.state["out_toks"])
            lp_host = np.asarray(self.state["acc_lp"])
        toks = np.asarray(out_host[slot, :meta["length"]], np.int32)
        ll = float(lp_host[slot])
        for pid in self.block_tables[slot]:
            if pid != self.trash_page:
                self.free_pages.append(int(pid))
        self.block_tables[slot] = self.trash_page
        self.true_lens[slot] = 0
        self._slot_meta[slot] = None
        self._results[meta["tag"]] = (toks, ll)

    def _pump(self, params, temperature):
        while self._pending and self.free_slots():
            self._admit(self._pending.popleft(), params, temperature)

    def step(self, params, temperature):
        """Advance every active slot one token; retire finished rows."""
        t0 = time.perf_counter()
        with obs.span("paged.step"):
            self.state = self._step_fn(params, self.state,
                                       self._device_temp(temperature))
            finished = []
            active = 0
            for slot, meta in enumerate(self._slot_meta):
                if meta is None:
                    continue
                active += 1
                self.true_lens[slot] += 1
                meta["done"] += 1
                if meta["done"] >= meta["length"]:
                    finished.append(slot)
            self.steps += 1
            self.slot_steps += active
            if finished:
                out_host = np.asarray(self.state["out_toks"])
                lp_host = np.asarray(self.state["acc_lp"])
                for slot in finished:
                    self._retire(slot, out_host, lp_host)
        self.step_host_s += time.perf_counter() - t0

    def run(self, params, temperature, specs=(), poll=None):
        """Decode ``specs`` (plus anything ``poll`` injects) to completion.

        ``poll(free_slots) -> [spec dicts]`` is called once per loop
        iteration — the live-admission hook: rows it returns join the
        *running* batch at the next admission, and the engine only shuts
        down after a final poll comes back empty. Returns {tag: (tokens
        (L,) i32, loglik float)} for every row retired this run."""
        for s in specs:
            self.submit(**s)
        while True:
            self._pump(params, temperature)
            if poll is not None:
                new = list(poll(self.free_slots()))
                if new:
                    for s in new:
                        self.submit(**s)
                    self._pump(params, temperature)
            if not self.active_slots() and not self._pending:
                break
            if self.active_slots():
                self.step(params, temperature)
        out, self._results = self._results, {}
        return out


# ---------------------------------------------------------------------------
# FoldScore
# ---------------------------------------------------------------------------


def init_foldscore(key, cfg):
    ks = split_keys(key, ["lm", "plddt", "ptm", "pae_l", "pae_r", "tgt"])
    params = lm_mod.init_lm(ks["lm"], cfg)
    d = cfg.d_model
    params["heads"] = {
        "plddt": dense_init(ks["plddt"], (d, 1), d, jnp.float32),
        "ptm": dense_init(ks["ptm"], (d, 1), d, jnp.float32),
        "pae_l": dense_init(ks["pae_l"], (d, 32), d, jnp.float32),
        "pae_r": dense_init(ks["pae_r"], (d, 32), d, jnp.float32),
        "tgt": dense_init(ks["tgt"], (16, d), 16, jnp.float32),
    }
    return params


def _foldscore_trunk(params, seqs, target, cfg):
    """Shared trunk: embedded complex + target descriptor through the
    transformer stack. Returns final hidden states (B, L, d) in fp32. The
    stack is causal (dense-family ``attn`` layers), so appending pad tokens
    to a row leaves the hidden states at its real positions bit-identical —
    the property the masked scorer relies on."""
    from repro.models.common import embed_tokens, norm_fwd as _norm
    from repro.models import blocks as blk
    x = embed_tokens(params["embedding"], seqs, cfg)
    x = x + jnp.einsum("bf,fd->bd", target.astype(jnp.float32),
                       params["heads"]["tgt"])[:, None].astype(x.dtype)
    ctx = {"positions": jnp.arange(seqs.shape[1]), "enc_out": None}
    for seg, (kinds, _) in zip(params["segments"], cfg.segments):
        x, _ = blk.segment_fwd(seg, x, kinds, ctx, cfg)
    return _norm(params["final_norm"], x, cfg).astype(jnp.float32)


def _pae_logits(params, x):
    """Full inter-residue pAE matrix (B, L, L) from trunk states."""
    h = params["heads"]
    zl = jnp.einsum("bld,dk->blk", x, h["pae_l"])
    zr = jnp.einsum("bld,dk->blk", x, h["pae_r"])
    return 30.0 * jax.nn.sigmoid(
        jnp.einsum("bik,bjk->bij", zl, zr) / np.sqrt(32.0))


def foldscore_fwd(params, seqs, target, cfg, chain_split: int):
    """seqs (B,L) i32 complex sequence; target (B,16) target descriptor;
    chain_split = index separating receptor from peptide chain.
    Returns FoldMetrics."""
    x = _foldscore_trunk(params, seqs, target, cfg)
    h = params["heads"]
    plddt_res = 100.0 * jax.nn.sigmoid(
        jnp.einsum("bld,d->bl", x, h["plddt"][:, 0]))           # (B,L)
    plddt = plddt_res.mean(-1)
    ptm = jax.nn.sigmoid(jnp.einsum("bld,d->bl", x, h["ptm"][:, 0]).mean(-1))
    pae_full = _pae_logits(params, x)                           # (B,L,L)
    inter = pae_full[:, :chain_split, chain_split:]
    pae = 0.5 * (inter.mean((-2, -1))
                 + pae_full[:, chain_split:, :chain_split].mean((-2, -1)))
    return FoldMetrics(plddt=plddt, ptm=ptm, pae=pae)


def foldscore_fwd_masked(params, seqs, target, seq_lens, chain_splits, cfg):
    """Masked scorer for dense mixed-length batches.

    seqs (B, Lpad) i32, rows padded past their true length; seq_lens (B,)
    i32 per-row complex length; chain_splits (B,) i32 per-row receptor
    length (traced, so mixed receptor lengths share ONE executable —
    unlike ``foldscore_fwd``'s static ``chain_split``). Pad positions are
    excluded from the pLDDT/pTM means and from both inter-chain pAE means,
    so a padded row's metrics match scoring it alone at its true length
    (the trunk is causal; see ``_foldscore_trunk``). Returns FoldMetrics.
    """
    x = _foldscore_trunk(params, seqs, target, cfg)
    h = params["heads"]
    pos = jnp.arange(seqs.shape[1])[None, :]                    # (1, L)
    valid = (pos < seq_lens[:, None]).astype(jnp.float32)       # (B, L)
    n_valid = jnp.maximum(valid.sum(-1), 1.0)
    plddt_res = 100.0 * jax.nn.sigmoid(
        jnp.einsum("bld,d->bl", x, h["plddt"][:, 0]))
    plddt = (plddt_res * valid).sum(-1) / n_valid
    ptm = jax.nn.sigmoid(
        (jnp.einsum("bld,d->bl", x, h["ptm"][:, 0]) * valid).sum(-1)
        / n_valid)
    pae_full = _pae_logits(params, x)                           # (B,L,L)
    receptor = (pos < chain_splits[:, None]).astype(jnp.float32)
    peptide = valid * (1.0 - receptor)
    den = jnp.maximum(receptor.sum(-1) * peptide.sum(-1), 1.0)
    rp = jnp.einsum("bij,bi,bj->b", pae_full, receptor, peptide) / den
    pr = jnp.einsum("bij,bi,bj->b", pae_full, peptide, receptor) / den
    return FoldMetrics(plddt=plddt, ptm=ptm, pae=0.5 * (rp + pr))
