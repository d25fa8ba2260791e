"""Executor layer: rows per fold-stage ``predict_batch`` dispatch in
the window, cross-tenant fusion included, from the payload's
``batch_log``."""

from bench import readers


def read(ctx):
    return readers.rows_per_dispatch(ctx["run"].dispatches["fold"])
