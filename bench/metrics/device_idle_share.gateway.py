"""Device layer, gateway cells: as ``device_idle_share`` (%)."""

from bench import readers


def read(ctx):
    return readers.idle_share(ctx)
