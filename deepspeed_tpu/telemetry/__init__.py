"""Unified runtime telemetry (ISSUE 8): metrics registry, step-phase
spans, schema-versioned event log, pluggable exporters.

One import surface for everything the engine, the resilience/elastic
layers, bench.py, and the ``ds_tpu_metrics`` CLI share:

- :class:`MetricsRegistry` — typed counters/gauges/histograms + labels
  (`registry.py`).
- :class:`TelemetrySession` / :func:`get_default_session` — registry +
  event log + span API bundled per run (`session.py`).
- :class:`Span` — the span (without a session: the telemetry-off
  path); `spans.py` also has the one clock of spans and request stamps
  (``spans.clock``), the process-wide ring every closed span lands in
  (``spans.recent`` / ``spans.record``) and the collector's callback;
  `compile_cache.py` puts every trace, lowering and compile on that
  ring.
- :data:`SCHEMA_VERSION` — the event-log version tag, also embedded in
  ``ds_tpu_audit --json`` so audits and telemetry join (`events.py`).
- The synchronized timers and the trace-window profiler that moved here
  from ``utils/`` (`timers.py`, `profiler.py`).
- The runtime-forensics layer (ISSUE 12): :class:`FlightRecorder`
  (black-box ring + atomic crash dumps, `flight.py`),
  :class:`HangWatchdog` / :class:`StepAnomalyDetector` (hang detection
  + anomaly-triggered trace capture, `watchdog.py`).

See docs/observability.md for the config block and event schema.
"""

from deepspeed_tpu.telemetry.events import EventLog, SCHEMA_VERSION  # noqa: F401
from deepspeed_tpu.telemetry.exporters import (  # noqa: F401
    ConsoleExporter, JsonlExporter, PrometheusTextfileExporter)
from deepspeed_tpu.telemetry.flight import (  # noqa: F401
    FlightRecorder, install_crash_hooks, uninstall_crash_hooks)
from deepspeed_tpu.telemetry.profiler import (  # noqa: F401
    TraceProfiler, device_report)
from deepspeed_tpu.telemetry.watchdog import (  # noqa: F401
    HangWatchdog, StepAnomalyDetector)
from deepspeed_tpu.telemetry.registry import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry)
from deepspeed_tpu.telemetry.session import (  # noqa: F401
    TelemetrySession, get_default_session, set_default_session)
from deepspeed_tpu.telemetry.spans import Span  # noqa: F401
from deepspeed_tpu.telemetry.timers import (  # noqa: F401
    SynchronizedWallClockTimer, ThroughputTimer)

__all__ = [
    "ConsoleExporter",
    "Counter",
    "EventLog",
    "FlightRecorder",
    "Gauge",
    "HangWatchdog",
    "Histogram",
    "JsonlExporter",
    "MetricsRegistry",
    "PrometheusTextfileExporter",
    "SCHEMA_VERSION",
    "Span",
    "StepAnomalyDetector",
    "SynchronizedWallClockTimer",
    "TelemetrySession",
    "ThroughputTimer",
    "TraceProfiler",
    "device_report",
    "get_default_session",
    "install_crash_hooks",
    "set_default_session",
    "uninstall_crash_hooks",
]
