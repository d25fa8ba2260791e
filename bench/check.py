"""The comparison that decides ``correct``.

A sample of what the timed path produced, drawn from the seed after the
window has closed, is run through the plain float32 reference of each
model's architecture (``archs/<arch>.py``) on the pipeline's own inputs:

``gen_ll_gap``   widest gap, in nats, between a sampled candidate's
                 log-likelihood as the program returned it and the
                 reference's log-likelihood of the same tokens on the same
                 structure prefix;
``score_gap``    widest gap between a score row as the program returned
                 it and the reference's scores of the same complex at its
                 true length, each metric over its range (pLDDT / 100,
                 pTM, pAE / 30).

The sample always holds the longest candidate and the longest complex.
The reference runs in fixed blocks of rows padded to a multiple of 32
positions, so its programs have few shapes and stay in the compile
cache.
"""

from __future__ import annotations

import numpy as np

BLOCK = 8
RANGES = np.asarray([100.0, 1.0, 30.0])


def _sample(recs, n, length, rng):
    if not recs:
        return []
    longest = max(range(len(recs)), key=lambda i: length(recs[i]))
    rest = [i for i in range(len(recs)) if i != longest]
    take = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [recs[longest]] + [recs[rest[int(i)]] for i in sorted(take)]


def _pad(n, to=32):
    return -(-n // to) * to


def _blocks(items):
    for i in range(0, len(items), BLOCK):
        chunk = items[i:i + BLOCK]
        yield chunk + [chunk[-1]] * (BLOCK - len(chunk)), len(chunk)


def ref_lls(cands, params, m: dict, arch, quant=None) -> np.ndarray:
    """The reference's log-likelihood of each candidate's tokens on its
    structure prefix. ``cands``: (backbone prefix (P, 16), tokens (T,));
    ``arch``: the generator's architecture module."""
    mt = tuple(sorted(m.items()))
    T = _pad(max(len(c[1]) for c in cands))
    out = []
    for chunk, real in _blocks(cands):
        toks = np.zeros((BLOCK, T), np.int32)
        for r, c in enumerate(chunk):
            toks[r, :len(c[1])] = c[1]
        lp = np.asarray(arch.token_logprobs(
            params, np.stack([c[0] for c in chunk]), toks, m=mt,
            quant=quant), np.float64)
        out += [lp[r, :len(c[1])].sum() for r, c in enumerate(chunk[:real])]
    return np.asarray(out)


def ref_scores(rows, params, m: dict, arch, quant=None) -> np.ndarray:
    """The reference's (pLDDT, pTM, pAE) of each complex at its true
    length. ``rows``: (complex (L,), target (16,), chain split);
    ``arch``: the scorer's architecture module."""
    mt = tuple(sorted(m.items()))
    L = _pad(max(len(r[0]) for r in rows))
    out = []
    for chunk, real in _blocks(rows):
        seqs = np.zeros((BLOCK, L), np.int32)
        for i, r in enumerate(chunk):
            seqs[i, :len(r[0])] = r[0]
        got = arch.fold_metrics(
            params, seqs, np.stack([r[1] for r in chunk]),
            np.asarray([len(r[0]) for r in chunk], np.int32),
            np.asarray([r[2] for r in chunk], np.int32), m=mt, quant=quant)
        out += list(np.asarray(got, np.float64)[:real])
    return np.asarray(out)


def samples(recorder, plan: dict, rng):
    """The records to compare: ``plan["generate"]`` candidates and
    ``plan["score"]`` score rows, each set with its longest member."""
    cands = [(g, k) for g in recorder.gen for k in range(len(g["tokens"]))]
    cands = _sample(cands, int(plan["generate"]),
                    lambda c: len(c[0]["tokens"][c[1]]), rng)
    rows = _sample(recorder.scores, int(plan["score"]),
                   lambda r: len(r["seq"]), rng)
    return cands, rows


def compare(recorder, weights, roles: dict, plan: dict, seed_words,
            control: bool = False) -> dict:
    """The numbers compared, each the widest gap over the sample against
    the float32 reference. ``roles`` maps (kind, param-set namespace) to
    (role, sizes, architecture module). With ``control`` the program's
    answers are replaced by the control's: the reference computed at
    float8 (``quant="fp8"``) on the same inputs, one precision step below
    the configuration's bfloat16 -- the reading that sets a limit's upper
    end."""
    rng = np.random.default_rng(np.asarray(seed_words, np.uint32))
    cands, rows = samples(recorder, plan, rng)
    gen, score = [], []
    for ns in sorted({g["ns"] for g, _ in cands}):
        role, m, arch = roles["generator", ns]
        sel = [(g["backbone"], g["tokens"][k], g["ll"][k])
               for g, k in cands if g["ns"] == ns]
        want = ref_lls(sel, weights[role], m, arch)
        got = (ref_lls(sel, weights[role], m, arch, "fp8") if control
               else np.asarray([c[2] for c in sel]))
        gen.append(np.abs(got - want).max())
    for ns in sorted({r["ns"] for r in rows}):
        role, m, arch = roles["scorer", ns]
        sel = [(r["seq"], r["target"], r["split"]) for r in rows
               if r["ns"] == ns]
        want = ref_scores(sel, weights[role], m, arch)
        got = (ref_scores(sel, weights[role], m, arch, "fp8") if control
               else np.asarray([r["metrics"] for r in rows
                                if r["ns"] == ns], np.float64))
        score.append((np.abs(got - want) / RANGES).max())
    out = {}
    if gen:
        out["gen_ll_gap"] = float(max(gen))
    if score:
        out["score_gap"] = float(max(score))
    return out
