"""The benchmark's own copy of the design-task generator: PDZ-like
receptor backbones with a fixed target peptide, as the program's
``repro.data.synthetic.protein_design_tasks`` draws them (the program
keeps its copy; see PERF.md, Open questions). At set-up the harness checks
that the program draws the same structures for the cell's spec.
"""

from __future__ import annotations

import numpy as np

PDZ_NAMES = ("NHERF3", "HTRA1", "SCRIB", "SHANK1")


def design_tasks(n_tasks, *, receptor_len=48, peptide_len=10, feat_dim=16,
                 seed=0):
    """``n_tasks`` tasks; ``receptor_len`` an int or per-task lengths,
    cycled. Each task: name, backbone (receptor + peptide, feat_dim),
    target descriptor (feat_dim,), receptor_len, peptide_len and the
    fixed peptide's tokens."""
    rng = np.random.default_rng(seed)
    lens = (list(receptor_len) if isinstance(receptor_len, (tuple, list))
            else [receptor_len])
    target = rng.normal(size=(feat_dim,)).astype(np.float32)
    peptide = rng.integers(1, 21, size=(peptide_len,)).astype(np.int32)
    tasks = []
    for i in range(n_tasks):
        rl = int(lens[i % len(lens)])
        backbone = rng.normal(size=(rl + peptide_len, feat_dim)
                              ).astype(np.float32)
        jitter = rng.normal(size=(feat_dim,)).astype(np.float32)
        tasks.append({
            "name": PDZ_NAMES[i] if i < len(PDZ_NAMES) else f"PDZ{i:03d}",
            "backbone": backbone,
            "target": target + 0.1 * jitter,
            "receptor_len": rl,
            "peptide_len": peptide_len,
            "peptide_tokens": peptide,
        })
    return tasks


def same_tasks(a, b) -> bool:
    """Two task lists hold the same structures, bit for bit."""
    if len(a) != len(b):
        return False
    for x, y in zip(a, b):
        if set(x) != set(y):
            return False
        for k in x:
            if isinstance(x[k], np.ndarray):
                if x[k].dtype != np.asarray(y[k]).dtype or not np.array_equal(
                        x[k], y[k]):
                    return False
            elif x[k] != y[k]:
                return False
    return True


def check_program(program_fn, spec: dict):
    """Raise unless ``program_fn`` (the program's generator) draws what
    this copy draws for ``spec`` (structures, receptor_len, peptide_len,
    seed)."""
    kw = dict(receptor_len=spec["receptor_len"],
              peptide_len=spec["peptide_len"], seed=spec["seed"])
    if not same_tasks(program_fn(spec["structures"], **kw),
                      design_tasks(spec["structures"], **kw)):
        raise ValueError("the program's structure generator draws other "
                         f"structures than the benchmark's for {spec}")
