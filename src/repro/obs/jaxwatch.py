"""JAX compile/retrace watcher: first-class metric series for XLA
compilation events.

``CompileWatcher`` bridges two probe styles into the metrics registry:

* ``jax.monitoring`` listeners: every trace/lower/compile duration event
  increments ``jax.compiles{event=...}`` and feeds
  ``jax.compile_s{event=...}`` (``backend_compile_duration`` is one XLA
  compile or persistent-cache load, ``cache_retrieval_time_sec`` one
  cache hit) — catching *every* trace/compile in the process, including
  retraces the payload layer never sees. Listener registration is
  process-global and cannot be undone, so one module-level listener fans
  out to whichever watchers are currently active (the context manager
  toggles an active flag instead of re-registering).
* ``trace_counts``-style probes: explicit counters owned by long-lived
  engines (e.g. ``PagedDecodeEngine.trace_counts``) — ``absorb_counts``
  folds their deltas in under ``jax.traces{probe=..., event=...}``.

Payload-layer compile walls (``core.payload.compile_log``) are folded in
the same way by the session at report time (``absorb_compile_log``).
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

_lock = threading.Lock()
_active: list = []            # active CompileWatcher instances
_listener_installed = False

# jax.monitoring event substrings that mean "XLA compiled something"
_COMPILE_MARKERS = ("compil", "trace", "lower")


def _on_event_duration(event: str, duration: float, **kw) -> None:
    if not any(m in event for m in _COMPILE_MARKERS):
        return
    with _lock:
        watchers = list(_active)
    for w in watchers:
        w._record(event, duration)


def _install_listener() -> None:
    """Register the module-level jax.monitoring listener once."""
    global _listener_installed
    with _lock:
        if _listener_installed:
            return
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_event_duration)
        _listener_installed = True


class CompileWatcher:
    """Context manager streaming XLA compile events into a registry::

        with CompileWatcher(registry):
            ...  # jitted calls; compiles land in jax.compiles / jax.compile_s

    Inactive watchers cost nothing.
    """

    def __init__(self, registry):
        self.registry = registry
        self._counts_seen: Dict[tuple, float] = {}

    def _record(self, event: str, duration: float) -> None:
        short = event.rsplit("/", 1)[-1] or event
        self.registry.counter("jax.compiles", event=short).inc()
        self.registry.histogram("jax.compile_s",
                                event=short).observe(float(duration))

    def absorb_counts(self, probe: str, counts: Dict[str, int]) -> None:
        """Fold a ``trace_counts``-style monotonically-growing counter dict
        into the registry (delta since this watcher last saw the probe)."""
        for name, n in counts.items():
            key = (probe, name)
            prev = self._counts_seen.get(key, 0)
            if n > prev:
                self.registry.counter("jax.traces", probe=probe,
                                      event=name).inc(n - prev)
                self._counts_seen[key] = n

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
        _install_listener()
        with _lock:
            _active.append(self)
        return self

    def __exit__(self, *exc) -> None:
        with _lock:
            if self in _active:
                _active.remove(self)
