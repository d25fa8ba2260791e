"""Scheduler layer: 95th percentile of the executor's
``task.queue_wait_s{kind=generate_batch}`` histogram over the window's
campaigns, host clock (ms)."""

from bench import readers


def read(ctx):
    v = readers.histogram_p95(ctx["run"], "task.queue_wait_s",
                              kind="generate_batch")
    return None if v is None else 1000.0 * v
