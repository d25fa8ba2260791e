"""The backend-aware ``interpret`` resolution every kernel wrapper shares."""


def resolve_interpret(interpret=None) -> bool:
    """Resolve an ``interpret=None`` kernel flag: an explicit argument
    wins; otherwise interpret exactly when the backend is not a TPU (so
    CPU tests run every Pallas kernel unflagged, and a TPU always runs
    the compiled kernel).

    Must be called *outside* jit — the result feeds a static pallas_call
    argument, and resolving inside a trace would freeze the backend state
    of the first call into the cached executable."""
    if interpret is not None:
        return bool(interpret)
    import jax
    return jax.default_backend() != "tpu"
