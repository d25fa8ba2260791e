"""Scheduler layer: 95th percentile of the gateway executor's
``task.queue_wait_s`` histogram over every task kind, host clock (ms)."""

from bench import readers


def read(ctx):
    v = readers.histogram_p95(ctx["run"], "task.queue_wait_s")
    return None if v is None else 1000.0 * v
