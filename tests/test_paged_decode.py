"""Paged continuous-decode tests: interpret-mode kernel parity vs the
vectorized fallback and a dense oracle, paged vs dense ``lm.decode_step``
model parity, engine page-pool round-trip (retire frees pages, re-admit
reuses them), composition independence (identical tokens solo vs joining
mid-flight) with the zero-recompile probe, the payload/executor live
admission path, solo-predict length bucketing, and the interpret rule."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.configs.registry import get_reduced
from repro.core import ProteinPayload, ResourceRequest, Task
from repro.core.payload import _fold_in_keys, gen_batch_log
from repro.kernels import paged_attention as pa
from repro.kernels._compat import resolve_interpret
from repro.models import lm
from repro.models import protein as prot
from repro.runtime import AsyncExecutor, DeviceAllocator

CFG = dataclasses.replace(get_reduced("progen-s"), compute_dtype="float32")
PARAMS = prot.init_progen(jax.random.PRNGKey(0), CFG)
S0 = CFG.frontend_seq + 1                     # patches + BOS prompt


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------

def _rand_paged(rng, B, KV, G, hd, page, maxp, P):
    q = rng.normal(size=(B, KV, G, hd)).astype(np.float32)
    kp = rng.normal(size=(P, KV, page, hd)).astype(np.float32)
    vp = rng.normal(size=(P, KV, page, hd)).astype(np.float32)
    bt = rng.integers(0, P, size=(B, maxp)).astype(np.int32)
    return q, kp, vp, bt


def _dense_oracle(q, kp, vp, bt, lens, page):
    """Per-row gather + plain softmax in numpy/f64."""
    B, KV, G, hd = q.shape
    out = np.zeros_like(q)
    for b in range(B):
        L = int(lens[b])
        if L == 0:
            continue
        k = np.concatenate([kp[p] for p in bt[b]], axis=1)[:, :L]  # KV,L,hd
        v = np.concatenate([vp[p] for p in bt[b]], axis=1)[:, :L]
        s = np.einsum("kgh,klh->kgl", q[b].astype(np.float64),
                      k.astype(np.float64)) / np.sqrt(hd)
        p_ = np.exp(s - s.max(-1, keepdims=True))
        p_ /= p_.sum(-1, keepdims=True)
        out[b] = np.einsum("kgl,klh->kgh", p_, v.astype(np.float64))
    return out


@pytest.mark.parametrize("B,KV,G,hd,page,maxp", [
    (4, 2, 2, 16, 4, 3), (3, 1, 4, 32, 8, 2), (6, 2, 1, 16, 8, 4),
])
def test_paged_kernel_parity(B, KV, G, hd, page, maxp):
    rng = np.random.default_rng(3)
    q, kp, vp, bt = _rand_paged(rng, B, KV, G, hd, page, maxp, P=maxp * B)
    lens = rng.integers(0, maxp * page + 1, size=B).astype(np.int32)
    lens[0] = 0                               # inactive slot
    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(lens))
    kern = np.asarray(pa.paged_decode_bkgh(*args, page_size=page,
                                           interpret=True))
    ref = np.asarray(pa.paged_decode_ref(*args, page_size=page))
    oracle = _dense_oracle(q, kp, vp, bt, lens, page)
    assert_allclose(kern, oracle, atol=1e-5, rtol=1e-5)
    assert_allclose(ref, oracle, atol=1e-5, rtol=1e-5)
    assert_allclose(kern, ref, atol=1e-5, rtol=1e-5)
    assert np.all(kern[lens == 0] == 0.0)     # inactive rows exactly zero


# ---------------------------------------------------------------------------
# model-level: paged vs dense decode
# ---------------------------------------------------------------------------

def test_paged_model_matches_dense_decode_step():
    """Same prompts decoded greedily through the dense cache path
    (``lm.prefill`` + ``lm.decode_step``) and through the paged path with
    a scrambled page layout: per-step logits agree to 1e-5 in fp32."""
    B, steps, page = 2, 5, 4
    rng = np.random.default_rng(11)
    bbs = rng.normal(size=(B, CFG.frontend_seq, 16)).astype(np.float32)
    patches = prot.encode_structure(PARAMS, jnp.asarray(bbs), CFG)
    bos = jnp.zeros((B, 1), jnp.int32)
    batch = {"inputs": bos, "patches": patches}

    d_logits, d_caches, t0 = lm.prefill(PARAMS, batch, CFG,
                                        cache_len=S0 + steps)
    assert t0 == S0

    maxp = -(-(S0 + steps) // page)
    n_pages = B * maxp
    p_caches = lm.init_paged_caches(CFG, n_pages + 1, page)
    # interleaved page layout: row 0 gets even pages, row 1 odd ones —
    # physical placement must not affect the math
    bt = np.stack([np.arange(0, 2 * maxp, 2, dtype=np.int32),
                   np.arange(1, 2 * maxp, 2, dtype=np.int32)])
    bt_j = jnp.asarray(bt)
    p_logits, p_caches = lm.paged_prefill(PARAMS, batch, CFG, p_caches, bt_j)
    assert_allclose(np.asarray(p_logits, np.float32),
                    np.asarray(d_logits, np.float32), atol=1e-5, rtol=1e-5)

    tok = jnp.argmax(d_logits[:, :CFG.vocab_size], -1)[:, None].astype(
        jnp.int32)
    for i in range(steps):
        d_logits, d_caches = lm.decode_step(PARAMS, d_caches, tok, S0 + i,
                                            CFG)
        pos = jnp.full((B,), S0 + i, jnp.int32)
        p_logits, p_caches = lm.paged_decode_step(
            PARAMS, p_caches, tok, pos, bt_j, pos + 1, CFG, interpret=True)
        assert_allclose(np.asarray(p_logits, np.float32),
                        np.asarray(d_logits, np.float32),
                        atol=1e-5, rtol=1e-5)
        tok = jnp.argmax(d_logits[:, :CFG.vocab_size], -1)[:, None].astype(
            jnp.int32)


# ---------------------------------------------------------------------------
# engine: round-trip, page reuse, composition independence
# ---------------------------------------------------------------------------

def _dense_rowsample(backbone, base_key, length, temp=1.0):
    """Oracle for one engine row: dense-cache decode with the engine's
    sampling scheme (token i drawn from ``fold_in(base_key, i)``)."""
    patches = prot.encode_structure(PARAMS, jnp.asarray(backbone)[None], CFG)
    batch = {"inputs": jnp.zeros((1, 1), jnp.int32), "patches": patches}
    logits, caches, _ = lm.prefill(PARAMS, batch, CFG, cache_len=S0 + length)
    key = jnp.asarray(base_key, jnp.uint32)
    toks, ll = [], 0.0
    tok = None
    for i in range(length):
        if i > 0:
            logits, caches = lm.decode_step(PARAMS, caches, tok, S0 + i - 1,
                                            CFG)
        lg = logits.astype(jnp.float32).at[:, CFG.vocab_size:].set(-1e30)
        t = jax.random.categorical(jax.random.fold_in(key, i), lg / temp, -1)
        ll += float(jnp.take_along_axis(jax.nn.log_softmax(lg, -1),
                                        t[:, None], -1)[0, 0])
        tok = t[:, None].astype(jnp.int32)
        toks.append(int(t[0]))
    return np.asarray(toks, np.int32), ll


def _specs(n, length, seed0=0):
    rng = np.random.default_rng(23)
    return [dict(backbone=rng.normal(
                     size=(CFG.frontend_seq, 16)).astype(np.float32),
                 key=np.asarray(jax.random.PRNGKey(seed0 + i), np.uint32),
                 length=length, tag=i) for i in range(n)]


def test_engine_round_trip_reuses_freed_pages():
    """3 rows through a 2-slot engine: the third admits only after a
    retirement and must decode on recycled pages, bit-identically to its
    dense oracle; the pool is fully restored afterwards."""
    eng = prot.PagedDecodeEngine(CFG, slots=2, max_new=6, interpret=True)
    specs = _specs(3, length=6)
    res = eng.run(PARAMS, 1.0, specs)
    assert set(res) == {0, 1, 2}
    for s in specs:
        toks, ll = _dense_rowsample(s["backbone"], s["key"], s["length"])
        got_toks, got_ll = res[s["tag"]]
        np.testing.assert_array_equal(got_toks, toks)
        assert abs(got_ll - ll) < 1e-3
    # spec 2 waited for a retirement: its pages came out of the free pool
    # some earlier row returned to it
    first_two = set(p for tag, pg in eng.alloc_log[:2] for p in pg)
    third = set(eng.alloc_log[2][1])
    assert third <= first_two
    assert sorted(eng.free_pages) == list(range(eng.n_pages))
    assert eng.trace_counts == {"admit": 1, "step": 1}


def test_engine_composition_independence_zero_recompiles():
    """A row's tokens are identical whether it decodes alone or is
    poll-injected into a half-finished batch — and the shared engine
    never retraces across either composition."""
    eng = prot.PagedDecodeEngine(CFG, slots=3, max_new=6, interpret=True)
    specs = _specs(3, length=6)
    solo = eng.run(PARAMS, 1.0, [specs[2]])[2]

    calls = []

    def poll(free):
        calls.append(free)
        return [specs[2]] if len(calls) == 3 else []

    res = eng.run(PARAMS, 1.0, specs[:2], poll=poll)
    assert len(calls) >= 3                    # injected mid-flight
    np.testing.assert_array_equal(res[2][0], solo[0])
    assert abs(res[2][1] - solo[1]) < 1e-4
    assert eng.trace_counts == {"admit": 1, "step": 1}


def test_engine_counts_steps_and_slot_steps():
    """``steps`` / ``slot_steps`` against arithmetic on the rows' lengths:
    admission samples a row's first token, so a row of length L is active
    for L - 1 steps. Rows of 3, 6 and 1 start together (the last retires
    at admission); a row of 7 is poll-injected before the third step, so
    the run takes 2 + 6 steps."""
    eng = prot.PagedDecodeEngine(CFG, slots=3, max_new=7, interpret=True)
    specs = [dict(s, length=n) for s, n in zip(_specs(4, 7), (3, 6, 1, 7))]
    calls = []

    def poll(free):
        calls.append(free)
        return [specs[3]] if len(calls) == 3 else []

    eng.run(PARAMS, 1.0, specs[:3], poll=poll)
    assert eng.slot_steps == (3 - 1) + (6 - 1) + 0 + (7 - 1)
    assert eng.steps == 2 + (7 - 1)
    eng.run(PARAMS, 1.0, [dict(specs[0], tag=9)])     # counts accumulate
    assert (eng.steps, eng.slot_steps) == (8 + 2, 13 + 2)


def test_engine_device_slot_state_matches_host_mirrors():
    """The device advances and retires slots by the host's own rule: after
    every step of a run (rows of 3, 6 and 1 tokens, and one of 7 injected
    by poll), the device's true lengths, block tables and base keys equal
    the host's mirrors, which the engine never reads back."""
    eng = prot.PagedDecodeEngine(CFG, slots=3, max_new=7, interpret=True)
    specs = [dict(s, length=n) for s, n in zip(_specs(4, 7), (3, 6, 1, 7))]
    orig, checked, calls = eng.step, [], []

    def step(params, temperature):
        orig(params, temperature)
        for name in ("true_lens", "block_tables", "base_keys"):
            np.testing.assert_array_equal(np.asarray(eng.state[name]),
                                          getattr(eng, name), err_msg=name)
        checked.append(eng.true_lens.copy())

    def poll(free):
        calls.append(free)
        return [specs[3]] if len(calls) == 3 else []

    eng.step = step
    res = eng.run(PARAMS, 1.0, specs[:3], poll=poll)
    assert len(checked) == 2 + (7 - 1) and set(res) == {0, 1, 2, 3}
    assert (checked[1] == 0).sum() == 2       # rows of 3 and 1 retired
    assert not checked[-1].any()
    assert (eng.block_tables == eng.trash_page).all()


def test_engine_step_makes_no_host_to_device_transfer():
    """A warm decode step takes everything from the device: the per-slot
    state is donated device state and the temperature is a device scalar,
    so a step (retirement included) runs under a guard that refuses every
    host-to-device transfer, explicit or implicit."""
    eng = prot.PagedDecodeEngine(CFG, slots=2, max_new=6, interpret=True)
    specs = _specs(2, length=6)
    for s in specs:
        eng.submit(**s)
    eng._pump(PARAMS, 1.0)
    eng.step(PARAMS, 1.0)                     # compiles the step
    with jax.transfer_guard_host_to_device("disallow_explicit"):
        for _ in range(6 - 2):
            eng.step(PARAMS, 1.0)
    assert not eng.active_slots()             # the last step retired both
    for s in specs:
        toks, _ = _dense_rowsample(s["backbone"], s["key"], s["length"])
        np.testing.assert_array_equal(eng._results[s["tag"]][0], toks)
    with jax.transfer_guard_host_to_device("disallow_explicit"):
        with pytest.raises(Exception, match="host-to-device"):
            jax.device_put(np.zeros(2, np.int32))
        with pytest.raises(Exception, match="host-to-device"):
            jax.jit(lambda x: x + 1)(np.float32(1.0))
    assert eng.trace_counts == {"admit": 1, "step": 1}


def test_gen_batch_log_carries_step_deltas():
    """Each paged dispatch's log record holds its own steps and active
    slot-steps (deltas of the engine's running counts), beside its slot
    count, and the host time of those steps: 1 row x 2 candidates of 6
    tokens take 5 steps in 4 slots."""
    pp = ProteinPayload(jax.random.PRNGKey(0), reduced=True, length=6)
    mesh = _Mesh()
    at = len(gen_batch_log)
    pp.generate_batch(mesh, _gen_payload(0))
    pp.generate_batch(mesh, _gen_payload(1))
    for rec in gen_batch_log[at:]:
        assert rec["bucket"] == 4
        assert (rec["steps"], rec["slot_steps"]) == (5, 2 * 5)
        assert rec["step_host_s"] > 0


# ---------------------------------------------------------------------------
# payload + executor: paged dispatch and live admission
# ---------------------------------------------------------------------------

class _Mesh:
    def __init__(self):
        self.devices = np.asarray(jax.devices()[:1])


def _gen_payload(seed, n=2, length=6):
    rng = np.random.default_rng(100 + seed)
    return {"backbones": rng.normal(size=(1, 20, 16)).astype(np.float32),
            "seeds": [seed], "n": n, "length": length,
            "temperature": 1.0, "decode": "paged"}


def test_payload_paged_rows_and_live_admission():
    pp = ProteinPayload(jax.random.PRNGKey(0), reduced=True, length=6)
    mesh = _Mesh()
    solo = pp.generate_batch(mesh, _gen_payload(0))
    assert len(solo["rows"]) == 1
    seqs, lls = solo["rows"][0]
    assert seqs.shape == (2, 6) and lls.shape == (2,)

    class _Port:                               # one queued compatible task
        def __init__(self, tasks):
            self.q = list(tasks)

        def take(self, k):
            out, self.q = self.q[:k], self.q[k:]
            return out

    port = _Port([Task(kind="generate_batch", payload=_gen_payload(1))])
    log_at = len(gen_batch_log)
    fused = pp.generate_batch(mesh, dict(_gen_payload(0), _admit=port))
    assert len(fused["rows"]) == 2
    # row 0 bit-identical to its solo dispatch: admission changed nothing
    np.testing.assert_array_equal(fused["rows"][0][0], seqs)
    assert_allclose(fused["rows"][0][1], lls, atol=1e-4)
    assert gen_batch_log[log_at]["decode"] == "paged"
    assert gen_batch_log[log_at]["admitted"] == 1
    # one engine executable serves every dispatch: no retraces
    dev = mesh.devices.flat[0]
    eng = pp._cache[("paged4_L6_p8", dev.id)]
    assert eng.trace_counts == {"admit": 1, "step": 1}


def test_paged_run_failure_fails_dispatch_and_evicts_engine(monkeypatch):
    """A paged engine that fails mid-run fails its dispatch (there is no
    dense re-run to hide it), and the half-updated engine leaves the cache
    so the retry builds a fresh one."""
    pp = ProteinPayload(jax.random.PRNGKey(0), reduced=True, length=6)
    mesh = _Mesh()
    key = ("paged4_L6_p8", mesh.devices.flat[0].id)

    def broken_run(self, *a, **kw):
        raise RuntimeError("decode step failed")

    monkeypatch.setattr(prot.PagedDecodeEngine, "run", broken_run)
    with pytest.raises(RuntimeError, match="decode step failed"):
        pp.generate_batch(mesh, _gen_payload(0))
    assert key not in pp._cache
    monkeypatch.undo()
    out = pp.generate_batch(mesh, _gen_payload(0))
    assert out["batch"]["decode"] == "paged" and len(out["rows"]) == 1
    assert key in pp._cache


def test_executor_live_admission_end_to_end():
    """A task submitted while a live-rule paged dispatch is running joins
    that dispatch through the AdmissionPort: both tasks complete, the
    leader's batch records the admission, and the late row's sequences
    are identical to what a solo dispatch yields."""
    pp = ProteinPayload(jax.random.PRNGKey(0), reduced=True, length=6)
    solo = pp.generate_batch(_Mesh(), _gen_payload(1))

    ex = AsyncExecutor(DeviceAllocator(jax.devices()[:1]), max_workers=1)
    pp.register_all(ex, decode_kernel=True)
    t2 = Task(kind="generate_batch", payload=_gen_payload(1),
              resources=ResourceRequest(n_devices=1, rows=1))
    started = []

    def wrapper(sm, payload):
        if not started:                       # queue t2 before decoding
            started.append(1)
            ex.submit(t2)
        return pp.generate_batch(sm, payload)

    ex.register("generate_batch", wrapper)    # keeps the live rule
    t1 = Task(kind="generate_batch", payload=_gen_payload(0),
              resources=ResourceRequest(n_devices=1, rows=1))
    ex.submit(t1)
    done = [ex.drain(timeout=60) for _ in range(2)]
    assert None not in done
    ex.shutdown()
    assert t1.result["batch"]["admitted"] == 1
    assert len(t1.result["rows"]) == 1 and len(t2.result["rows"]) == 1
    np.testing.assert_array_equal(t2.result["rows"][0][0],
                                  solo["rows"][0][0])


# ---------------------------------------------------------------------------
# satellites: solo-predict bucketing, interpret rule
# ---------------------------------------------------------------------------

def test_solo_predict_shares_bucketed_executable():
    pp = ProteinPayload(jax.random.PRNGKey(0), reduced=True,
                        length_buckets=(16, 32))
    mesh = _Mesh()
    rng = np.random.default_rng(5)

    def payload(L):
        return {"sequence": rng.integers(1, 20, size=L).astype(np.int32),
                "target": rng.normal(size=16).astype(np.float32),
                "receptor_len": 5, "seq_len": L}

    m10 = pp.predict(mesh, payload(10))
    m12 = pp.predict(mesh, payload(12))
    for m in (m10, m12):
        assert np.isfinite(m["plddt"]) and np.isfinite(m["pae"])
    keys = [k[0] for k in pp._cache if str(k[0]).startswith("predict")]
    assert keys == ["predict_mb1_L16"]        # one shared executable


def test_resolve_interpret_env_override(monkeypatch):
    """An explicit flag wins; otherwise interpret exactly off-TPU, so a
    TPU always runs the compiled kernel."""
    assert resolve_interpret(None) is (jax.default_backend() != "tpu")
    for backend, want in (("cpu", True), ("gpu", True), ("tpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert resolve_interpret(None) is want
        assert resolve_interpret(True) is True
        assert resolve_interpret(False) is False
