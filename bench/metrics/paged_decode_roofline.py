"""Kernel layer: the Pallas paged decode kernel's share of its roofline
over the profiled slice: the least time its calls could take (the larger
of operations over peak and bytes over HBM bandwidth, from each step's
live K/V lengths) over the device time of its events (%)."""

from bench import readers


def read(ctx):
    return readers.roofline(ctx, "paged_decode", "op_time",
                            readers.PAGED_KERNEL)
