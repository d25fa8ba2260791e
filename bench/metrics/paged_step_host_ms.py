"""Payload-model layer: host wall time of one paged decode step (ms): the
engines' ``step_host_s`` over their ``steps``, summed over the window's
paged ``generate_batch`` dispatches of the payload's ``gen_batch_log``.
A program whose log has no step time gives nothing."""


def read(ctx):
    secs = steps = 0
    for e in ctx["run"].dispatches["gen"]:
        if e.get("decode") == "paged" and "step_host_s" in e:
            secs += e["step_host_s"]
            steps += e["steps"]
    return 1000.0 * secs / steps if steps else None
