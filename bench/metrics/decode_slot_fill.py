"""Executor layer: the share of decode slots that carried a row, over the
window's paged ``generate_batch`` dispatches: the sum of the engine's
``slot_steps`` (active slots, summed over its steps) over the sum of
``steps`` x slots (``bucket``), from the payload's ``gen_batch_log`` (%).
A program whose log has no step counts gives nothing."""


def read(ctx):
    used = offered = 0
    for e in ctx["run"].dispatches["gen"]:
        if e.get("decode") == "paged" and "slot_steps" in e:
            used += e["slot_steps"]
            offered += e["steps"] * e["bucket"]
    return 100.0 * used / offered if offered else None
