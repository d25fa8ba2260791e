"""Traffic generator: turns a traffic file and a seed into the inputs of
one run. Imports neither JAX nor the program, so the HTTP load client can
use it in a process that never touches the chip.

A traffic file (``bench/traffic/<name>.json``) names the entry kind that
drives the system (``entry``) and the parameters of its load. Two
families are read here:

- closed loop (``campaign``): the campaign spec every client campaign
  runs, its seed derived from the run's seed and the campaign index;
- open loop (``arrivals``): Poisson arrivals at a fixed ``rate_per_s``
  from tenants with ``tenant_shares``, each campaign's two receptor
  lengths a pair of ``receptor_lens``; one fixed realization per mix
  (``arrival_seed``).
"""

from __future__ import annotations

import json
import os
from typing import Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_traffic(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        return json.load(f)


def sub_seed(seed: int, *tags: int, bits: int = 30) -> int:
    """A seed below ``2**bits`` derived from the run's seed (any size) and
    integer tags, so the program never sees a seed it cannot hold."""
    words = np.random.SeedSequence([int(seed), *map(int, tags)]
                                   ).generate_state(2, dtype=np.uint32)
    return int(((int(words[0]) << 32) | int(words[1])) % (1 << bits))


def campaign_spec(traffic: dict, seed: int, index: int, chips: int) -> dict:
    """The ``CampaignSpec`` fields of closed-loop campaign ``index``."""
    c = traffic["campaign"]
    return dict(c["spec"],
                structures=int(c["structures_per_chip"]) * int(chips),
                receptor_len=list(c["spec"]["receptor_len"]),
                seed=sub_seed(seed, 1, index))


def arrivals(traffic: dict, seed: int, seconds: float) -> List[Dict]:
    """The open-loop schedule of one run: every campaign due within
    ``seconds`` of the window's start, in due order, each with its due
    offset, tenant and the campaign body to POST.

    The schedule is one realization of the mix, drawn from its own
    ``arrival_seed`` and the same in every run: a Poisson process at
    ``rate_per_s`` conditioned on its count (exactly ``round(rate_per_s *
    seconds)`` arrivals at sorted uniform times), the tenants' counts
    following their shares and the receptor-length pairs cycling through
    every pair of ``receptor_lens``, both shuffled. The run's seed gives
    each campaign its own seed (its sampling streams) and the models their
    weights. So every seed offers the same work at the same times, and
    what the seed changes is the values computed."""
    a = traffic["arrivals"]
    n = int(round(float(a["rate_per_s"]) * float(seconds)))
    rng = np.random.default_rng(
        np.random.SeedSequence([int(a["arrival_seed"])]))
    due = np.sort(rng.uniform(0.0, float(seconds), size=n))
    tenants = list(a["tenant_shares"])
    shares = np.asarray([a["tenant_shares"][t] for t in tenants], float)
    counts = np.floor(shares / shares.sum() * n).astype(int)
    for j in np.argsort(-shares)[:n - counts.sum()]:
        counts[j] += 1
    who = rng.permutation(np.repeat(np.arange(len(tenants)), counts))
    lens = [int(v) for v in a["receptor_lens"]]
    pairs = [(x, y) for i, x in enumerate(lens) for y in lens[i + 1:]]
    picks = rng.permutation(np.arange(n) % len(pairs))
    out = []
    for i in range(n):
        body = dict(a["spec"], receptor_len=list(pairs[int(picks[i])]),
                    seed=sub_seed(seed, 3, i))
        out.append({"index": i, "due_s": float(due[i]),
                    "tenant": tenants[int(who[i])], "body": body})
    return out


def receptor_lens(traffic: dict) -> List[int]:
    """Every receptor length the traffic can send."""
    if "campaign" in traffic:
        return [int(v) for v in traffic["campaign"]["spec"]["receptor_len"]]
    return [int(v) for v in traffic["arrivals"]["receptor_lens"]]
