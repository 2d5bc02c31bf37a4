"""Telemetry subsystem: span tracing, metric streams, profiler hooks.

Three layers, all bitwise-inert when disabled (the same discipline as the
congestion and impairment engines — see ``docs/architecture.md`` §10):

* :mod:`repro.obs.trace` — the program's one instrument.  A
  ``span("name")`` block feeds a run's :class:`Stopwatch` (timing fields
  and counters), a process-wide :class:`TraceRecorder` that emits Chrome
  trace-event JSON (loadable in ``chrome://tracing`` / Perfetto, with
  correct thread attribution for the fleet's producer thread and the
  async JSONL exporter), and, under an active profile, the profiler trace
  as an annotation (waits excepted).  With neither installed a span is
  two ``perf_counter`` calls and two checks.
* :mod:`repro.obs.metrics` — per-frame metric streams.  The simulators'
  opt-in ``metrics=True`` path emits one :class:`MetricsFrame` per frame
  (per-server utilization/backlog, admission sheds, per-QoS-class
  satisfaction, assignment-tier histogram); the fleet stacks them across
  its ``lax.scan`` so there is **no host sync per frame** — frames drain
  once per window with the other scan outputs.  :class:`MetricsResult`
  aggregates (totals, percentiles, per-edge rollups) and exports JSONL.
* :mod:`repro.obs.profiler` — ``jax.profiler`` hooks.
  :func:`profile_trace` captures a device profile for a whole run and
  sets the flag that makes spans annotate it.
"""
from .trace import (
    CAT_BUILD,
    CAT_COMPILE,
    CAT_DISPATCH,
    CAT_GEN,
    CAT_IO,
    CAT_METRICS,
    CAT_SCHED,
    CAT_WAIT,
    Stopwatch,
    TraceRecorder,
    active_recorder,
    instant,
    recording,
    save_chrome_trace,
    span,
    start_trace,
    stop_trace,
    validate_chrome_trace,
)
from .metrics import (
    QOS_ACC_EDGES,
    MetricsFrame,
    MetricsResult,
)
from .export import AsyncJsonlWriter
from .profiler import profile_trace, profiling_active

__all__ = [
    "CAT_BUILD",
    "CAT_COMPILE",
    "CAT_DISPATCH",
    "CAT_GEN",
    "CAT_IO",
    "CAT_METRICS",
    "CAT_SCHED",
    "CAT_WAIT",
    "Stopwatch",
    "TraceRecorder",
    "active_recorder",
    "instant",
    "recording",
    "save_chrome_trace",
    "span",
    "start_trace",
    "stop_trace",
    "validate_chrome_trace",
    "QOS_ACC_EDGES",
    "MetricsFrame",
    "MetricsResult",
    "AsyncJsonlWriter",
    "profile_trace",
    "profiling_active",
]
