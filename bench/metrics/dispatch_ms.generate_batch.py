"""Executor layer: mean host wall time of a ``generate_batch`` dispatch,
from the executor's ``task.device_s{kind=generate_batch}`` histogram
(host time, despite the name) (ms)."""

from bench import readers


def read(ctx):
    v = readers.histogram_mean(ctx["run"], "task.device_s",
                               kind="generate_batch")
    return None if v is None else 1000.0 * v
