"""Payload-model layer, gateway cells: XLA compiles and compile-cache
loads from the window's start to the end of its drain."""


def read(ctx):
    return float(ctx["run"].window_compiles)
