#!/usr/bin/env python3
"""The benchmark's one command: run one cell on the chips of this machine.

    python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

Run from the root of a checkout. The cell, its configuration, traffic and
metrics are found by name through ``BENCHMARK.json``. With ``--trace 0``
the result carries the cell's end-to-end metrics; with ``--trace 1`` its
per-layer metrics, read from a profiled slice of the window.

The last line of standard output is the result object; the numbers
compared with the reference are also the last lines of standard error.
On a platform other than TPU, with fewer devices than the cell needs, or
with Pallas kernels that would run interpreted, it exits non-zero and
prints no result; so does a traced run whose trace has no ``bench.slice``
span or no operation of the cell's devices inside it.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # The compile cache stays inside the checkout at a fixed path, whatever
    # the machine sets: the program takes the directory it is given.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    from bench import harness
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             args.trace, t_start=T_START)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    print(f"[bench] set-up: {json.dumps(result.pop('setup_phases'))}; "
          f"compiles in the window: {result.pop('window_compiles')}",
          file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"[bench] check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
