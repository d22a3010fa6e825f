"""Unified telemetry: metric sinks, span tracing, and the process-global
:class:`Telemetry` registry.

Three layers, composable and individually optional:

* :mod:`repro.obs.sink` — the :class:`MetricSink` record protocol with
  JSONL (one flushed line per record: a killed run leaves a readable
  file), in-memory, and null backends.
* :mod:`repro.obs.trace` — wall-clock span/counter tracer exporting
  Chrome ``trace_event`` JSON (open in Perfetto / ``chrome://tracing``).
* :class:`Telemetry` — bundles a sink and a tracer behind no-op-safe
  ``emit`` / ``span`` / ``log`` entry points.  A process-global instance
  (:func:`configure` / :func:`get` / :func:`shutdown`) lets deep layers
  (train loop, serve engine, watchdog) report without plumbing a handle
  through every constructor.

The default global is a *null* Telemetry: ``emit`` drops the record,
``span`` opens only a ``jax.profiler.TraceAnnotation`` (one C++ call when
no profiler session is active), ``log`` only prints.  Hot-path call sites
therefore never need an ``if enabled`` guard.  Because every span is a
profiler annotation, a ``jax.profiler`` trace holds the program's spans on
the clock of the device's ops.  On-device tap *values* are
not routed through here at all (they live in the jitted step's metrics
output and are fetched at ``log_every`` boundaries by the train loop);
this layer only receives the already-fetched host scalars.
"""

from repro.obs.sink import (JsonlSink, MemorySink, MetricSink, NullSink,
                            Telemetry, configure, get, shutdown)
from repro.obs.trace import Tracer

__all__ = [
    "JsonlSink", "MemorySink", "MetricSink", "NullSink", "Telemetry",
    "Tracer", "configure", "get", "shutdown",
]
