#!/usr/bin/env python3
"""Readings that set the limits of the comparison: for each seed, one
short run of a cell at its own size and load, then the gaps of the
program and of the control on the same sample. Not part of the
benchmark's runs.

    python3 bench/control.py --workload NAME --seconds S --seeds 1 2 3 ...

The control is each model's float32 reference (its architecture's
module, ``archs/<arch>.py``) put in the program's place and computed at
float8 (``quant="fp8"``), one precision step below the bfloat16 the
configuration states. Prints one JSON line per seed: ``{"seed",
"program": {gap: value}, "control": {gap: value}}``.
The largest program reading over a dozen seeds or more is a limit's
lower reading; the smallest control reading its upper one.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def readings(name, seed, seconds, **kw) -> dict:
    import numpy as np
    from bench import check, harness
    r = harness.Run(name, seed, seconds, False, **kw)
    e2e = r.execute()
    words = np.random.SeedSequence([r.seed, 4]).generate_state(
        2, dtype=np.uint32)
    return {"seed": seed, "program": r.compare(),
            "control": check.compare(r.recorder, r.weights, r.roles(),
                                     r.config["check"], words, control=True),
            "e2e": e2e}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        print(json.dumps(readings(args.workload, seed, args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
