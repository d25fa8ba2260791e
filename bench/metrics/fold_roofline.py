"""Kernel layer: the masked scorer executables' share of their roofline
over the profiled slice: the least time of each call at its (rows,
length) shape over the device time of the ``foldscore_fwd_masked``
executables (%)."""

from bench import readers


def read(ctx):
    return readers.roofline(ctx, "fold", "module_time", readers.FOLD_MODULE)
