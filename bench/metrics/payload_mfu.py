"""Payload-model layer: useful model operations of the tokens sampled and
residues scored in the window, padding excluded, over window x chips x
the chip's bf16 peak (%)."""

from bench import readers


def read(ctx):
    return readers.payload_mfu(ctx)
