"""repro.obs — deterministic telemetry for the PS runtime, and names on
the profiler's clock.

Two clocks. The first three layers record the PS simulator's *virtual*
time (the DES's sim-seconds; wall-clock never appears):

* :mod:`repro.obs.spans` — a virtual-time span tracer (Chrome
  trace-event JSON, loadable in Perfetto) the runtime hangs pull RTTs,
  stalls, commit queues, retransmit ladders, crash/recovery windows
  and snapshot barriers onto;
* :mod:`repro.obs.metrics` — a registry of lazily-evaluated
  counters/gauges/histograms/series the runtime components register
  instruments into, from which ``PSRunResult.metrics`` is assembled;
* :mod:`repro.obs.stream` — pluggable per-round record sinks (JSONL,
  stdout live mode, in-process callback) carrying loss, per-block
  stationarity/residuals, queue depths, stall and transport totals.

**The contract: telemetry is inert by default and never perturbs the
schedule.** Recording uses the DES's virtual clock only, consumes no
rng, and schedules no events — a telemetry-on run is bitwise
identical (pallas) to a telemetry-off run, with equal fold logs and
makespan. ``scripts/ci.sh`` gates this on a chaos scenario.

The fourth, :mod:`repro.obs.profile`, runs on the *wall* clock of
``jax.profiler``'s own trace, which its host and device planes share:
``stage`` puts a ``jax.named_scope`` on each stage of the epoch (device
instructions carry it in their ``op_name``), ``host_span`` a
``TraceAnnotation`` on the PS runtime's host work, and
``install_compile_marker`` a zero-length span on every backend compile.
They record nothing unless a profiler trace is active, and like the rest
consume no rng and schedule nothing.

Metric, span, trace-event, scope and host-span names all validate
against :mod:`repro.obs.names` — the single registry that keeps the
vocabularies from drifting apart.
"""
from .metrics import MetricsRegistry, TimeSeries, hist
from .names import (DEVICE_SCOPES, HOST_SPANS, METRICS, SPAN_NAMES,
                    TRACE_EVENT_KINDS, TRANSPORT_EVENT_KINDS, validate_kind)
from .profile import host_span, install_compile_marker, stage
from .spans import SpanTracer
from .stream import (CallbackSink, JsonlSink, ROUND_RECORD_SCHEMA, Sink,
                     StdoutSink, make_sink, validate_record)
from .telemetry import Telemetry, as_telemetry

__all__ = [
    "MetricsRegistry", "TimeSeries", "hist",
    "DEVICE_SCOPES", "HOST_SPANS", "METRICS", "SPAN_NAMES",
    "TRACE_EVENT_KINDS", "TRANSPORT_EVENT_KINDS", "validate_kind",
    "SpanTracer", "host_span", "install_compile_marker", "stage",
    "CallbackSink", "JsonlSink", "ROUND_RECORD_SCHEMA", "Sink",
    "StdoutSink", "make_sink", "validate_record",
    "Telemetry", "as_telemetry",
]
