"""Self-observability layer: flight recorder, metrics, exporters, watchdog.

The package splits into four modules:

- :mod:`repro.obs.trace`   -- preallocated ring-buffer span/counter/event
  recorder (the flight recorder proper).  numpy + stdlib only, so hot
  paths anywhere in the tree can import it without cycles.
- :mod:`repro.obs.metrics` -- counters / gauges / fixed-log-bucket
  histograms with a process-global registry.
- :mod:`repro.obs.export`  -- Prometheus text snapshots and
  Chrome-trace-event JSON (loadable in Perfetto / chrome://tracing).
- :mod:`repro.obs.watch`   -- streaming signature watchdog over live
  ``FleetMonitor`` windows, plus the ``PartTimeSampler`` nvidia-smi-style
  negative baseline (imported lazily: it pulls in attrib/stream).

Instrumented call sites follow the pattern::

    from repro.obs import trace

    rec = trace.active()
    if rec is not None:
        rec.counter("rx.frames", float(n), track="rx")

which costs one module-attribute read and an ``is None`` test when
tracing is disabled (the default).  The serving path's parts wrap their
work in a program span instead, which also lands in any JAX profiler
trace::

    with trace.span("sched:step", live=n_live, slots=n_slots):
        ...
"""

from __future__ import annotations

from repro.obs import export, metrics, trace
from repro.obs.trace import TraceRecorder
from repro.obs.metrics import MetricsRegistry

__all__ = [
    "trace",
    "metrics",
    "export",
    "watch",
    "TraceRecorder",
    "MetricsRegistry",
    "enable",
    "disable",
]


def enable(capacity: int = 1 << 16) -> tuple[TraceRecorder, MetricsRegistry]:
    """Install a fresh global recorder + registry and return both."""
    rec = trace.install(TraceRecorder(capacity=capacity))
    reg = metrics.install(MetricsRegistry())
    return rec, reg


def disable() -> None:
    """Uninstall the global recorder and registry (tracing back to no-op)."""
    trace.uninstall()
    metrics.uninstall()


def __getattr__(name: str):
    if name == "watch":  # lazy: watch imports attrib/stream machinery
        import importlib

        return importlib.import_module("repro.obs.watch")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
