"""Executor layer: rows per paged ``generate_batch`` dispatch in the
window (live admissions included), from the payload's ``gen_batch_log``."""

from bench import readers


def read(ctx):
    return readers.rows_per_dispatch(
        [e for e in ctx["run"].dispatches["gen"]
         if e.get("decode") == "paged"])
