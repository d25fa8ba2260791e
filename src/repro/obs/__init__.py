"""Unified observability layer: lifecycle span tracing, program spans on
the profiler's clock, a metrics registry, and Perfetto/JSON export.

Three pillars, consumed by the runtime (executor/allocator/scheduler), the
coordinator, the session facade, and the benchmarks:

* ``trace`` — ``Tracer`` stamps every task with lifecycle spans
  (submitted -> queued -> granted -> dispatched -> device -> completed/
  preempted/retried), links coalesced rows to their fused-batch span, and
  records device-grant timelines; ``Telemetry`` bundles tracer + metrics +
  one injectable clock. ``span`` opens a scoped ``impress.*`` program span
  as a ``jax.profiler.TraceAnnotation``, on the profiler's clock rather
  than ``Telemetry.now``, so a device profile can attribute idle time to
  the program layer on the host.
* ``metrics`` — ``MetricsRegistry`` of counters/gauges/streaming
  histograms (p50/p95/max without storing samples); the executor/
  allocator/scheduler stat sections are rebuilt on it.
* ``export`` — Chrome/Perfetto ``trace_event`` JSON (device, stage-band,
  task-kind, and protocol tracks) + flat metrics JSON; ``jaxwatch`` adds
  compile series and interpreter GC pauses.
"""

from repro.obs.export import (trace_events, validate_trace, write_metrics,
                              write_trace)
from repro.obs.jaxwatch import CompileWatcher
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import LIFECYCLE, Telemetry, Tracer, span

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "CompileWatcher",
    "LIFECYCLE", "Telemetry", "Tracer", "span", "trace_events",
    "validate_trace", "write_metrics", "write_trace",
]
