"""Payload-model layer, gateway cells: as ``payload_mfu``, over the
window's campaigns from the window's start to the last one's end (%)."""

from bench import readers


def read(ctx):
    return readers.payload_mfu(ctx)
