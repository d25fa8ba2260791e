"""Device layer: 100 x (1 - busy / slice) over the profiled slice, busy
being the union of the device's operation intervals, averaged over the
cell's devices (%)."""

from bench import readers


def read(ctx):
    return readers.idle_share(ctx)
