"""Payload-model layer: XLA compiles and compile-cache loads inside the
window (``CompileWatcher``, ``backend_compile_duration`` events)."""


def read(ctx):
    return float(ctx["run"].window_compiles)
