"""JAX compile watcher and interpreter GC pauses: first-class metric
series for the two things that stall the host without the program asking.

``CompileWatcher`` bridges these probes into the metrics registry:

* ``jax.monitoring`` listeners: every trace/lower/compile duration event
  increments ``jax.compiles{event=...}`` and feeds
  ``jax.compile_s{event=...}`` (``backend_compile_duration`` is one XLA
  compile or persistent-cache load, ``cache_retrieval_time_sec`` one
  cache hit) — catching *every* trace/compile in the process, including
  retraces the payload layer never sees.
* ``gc.callbacks``: every collection of the interpreter's garbage
  collector feeds ``gc.pause_s{generation=...}`` (seconds, host clock)
  and is recorded as an ``impress.gc`` program span (``obs.span``) on
  the profiler's clock, so a device profile shows which of the device's
  idle gaps were GC pauses.

Both hooks are process-global and cannot be undone, so one module-level
listener of each kind fans out to whichever watchers are currently active
(the context manager toggles an active flag instead of re-registering);
with no watcher active a collection costs one list check.

Payload-layer compile walls (``core.payload.compile_log``) are folded in
by the session at report time (``absorb_compile_log``).
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Dict, Optional

from repro.obs.trace import span

_lock = threading.Lock()
_active: list = []            # active CompileWatcher instances
_installed = False

# jax.monitoring event substrings that mean "XLA compiled something"
_COMPILE_MARKERS = ("compil", "trace", "lower")


def _on_event_duration(event: str, duration: float, **kw) -> None:
    if not any(m in event for m in _COMPILE_MARKERS):
        return
    with _lock:
        watchers = list(_active)
    for w in watchers:
        w._record(event, duration)


# the collection in progress: (span, start). The interpreter runs one
# collection at a time, its start and stop callbacks on one thread.
_gc_open: Optional[tuple] = None


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook. A collection can start inside any allocation,
    including one made while a metrics lock is held, so the hook takes no
    lock: it queues the pause on each active watcher, and ``flush`` folds
    the queue into the registry."""
    global _gc_open
    if phase == "start":
        if _active:
            sp = span("gc", generation=info["generation"])
            sp.__enter__()
            _gc_open = (sp, time.perf_counter())
        return
    if _gc_open is None:
        return
    sp, t0 = _gc_open
    _gc_open = None
    pause = time.perf_counter() - t0
    sp.__exit__(None, None, None)
    for w in list(_active):
        w._pauses.append((info["generation"], pause))


def _install_listeners() -> None:
    """Register the module-level jax.monitoring listener and GC callback
    once."""
    global _installed
    with _lock:
        if _installed:
            return
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_event_duration)
        gc.callbacks.append(_on_gc)
        _installed = True


class CompileWatcher:
    """Context manager streaming XLA compile events and GC pauses into a
    registry::

        with CompileWatcher(registry):
            ...  # compiles land in jax.compiles / jax.compile_s,
                 # collections in gc.pause_s

    Inactive watchers cost nothing.
    """

    def __init__(self, registry):
        self.registry = registry
        self._pauses: list = []       # (generation, seconds) not yet folded

    def _record(self, event: str, duration: float) -> None:
        short = event.rsplit("/", 1)[-1] or event
        self.registry.counter("jax.compiles", event=short).inc()
        self.registry.histogram("jax.compile_s",
                                event=short).observe(float(duration))

    def absorb_compile_log(self, log: Dict[str, list],
                           start: Optional[Dict[str, int]] = None) -> None:
        """Fold the payload layer's per-kind compile walls in
        (``core.payload.compile_log``); ``start`` holds per-kind entry
        counts at session start, so long-lived processes only count this
        run's compiles."""
        for kind, walls in log.items():
            new = walls[(start or {}).get(kind, 0):]
            if new:
                self.registry.counter("jax.payload_compiles",
                                      kind=kind).inc(len(new))
                h = self.registry.histogram("jax.payload_compile_s",
                                            kind=kind)
                for w in new:
                    h.observe(float(w))

    def __enter__(self) -> "CompileWatcher":
        _install_listeners()
        with _lock:
            _active.append(self)
        return self

    def flush(self) -> None:
        """Fold the queued GC pauses into ``gc.pause_s{generation=...}``
        (done on exit; a long-lived watcher calls it as it goes)."""
        if not self._pauses:
            return
        pending, self._pauses = self._pauses, []
        for gen, pause in pending:
            self.registry.histogram("gc.pause_s",
                                    generation=gen).observe(pause)

    def __exit__(self, *exc) -> None:
        with _lock:
            if self in _active:
                _active.remove(self)
        self.flush()
