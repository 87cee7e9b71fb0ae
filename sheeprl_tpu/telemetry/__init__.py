"""Unified telemetry subsystem: spans, XLA health counters, throughput/MFU,
and a JSONL event stream (see `howto/telemetry.md`).

The `Telemetry` facade replaces the per-loop `timer` + `MetricAggregator` +
`TensorBoardLogger` plumbing; the legacy `utils.timer` API remains as a shim
over `telemetry.spans`.
"""
from .facade import Telemetry
from .schema import EVENT_SCHEMAS, SCHEMA_VERSION, validate_event, validate_jsonl
from .sinks import ConsoleHeartbeat, JsonlSink, write_event
from .spans import GLOBAL_TRACKER, Span, SpanTracker
from .tracing import (
    RemoteProfiler,
    TraceContext,
    child_context,
    clock_record,
    make_traceparent,
    new_span_id,
    new_trace_id,
    open_process_stream,
    parse_traceparent,
    span_record,
)
from .throughput import (
    PEAK_FLOPS,
    ThroughputTracker,
    flops_of_lowered,
    measured_cpu_peak_flops,
    mfu,
    peak_flops_basis_for,
    peak_flops_for,
    peak_flops_record,
)
from .xla import (
    RETRACE_DETECTOR,
    RetraceDetector,
    compile_counters,
    device_memory_stats,
    instrument,
)

__all__ = [
    "Telemetry",
    "EVENT_SCHEMAS",
    "SCHEMA_VERSION",
    "validate_event",
    "validate_jsonl",
    "ConsoleHeartbeat",
    "JsonlSink",
    "write_event",
    "GLOBAL_TRACKER",
    "Span",
    "SpanTracker",
    "RemoteProfiler",
    "TraceContext",
    "child_context",
    "clock_record",
    "make_traceparent",
    "new_span_id",
    "new_trace_id",
    "open_process_stream",
    "parse_traceparent",
    "span_record",
    "PEAK_FLOPS",
    "ThroughputTracker",
    "flops_of_lowered",
    "measured_cpu_peak_flops",
    "mfu",
    "peak_flops_basis_for",
    "peak_flops_for",
    "peak_flops_record",
    "RETRACE_DETECTOR",
    "RetraceDetector",
    "compile_counters",
    "device_memory_stats",
    "instrument",
]
