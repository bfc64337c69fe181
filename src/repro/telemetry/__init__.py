"""Telemetry plane (ISSUE 9): the sixth plane, watching the other five.

`trace` records exact per-plane occupancy gauges + host timings per
tick; `advisor` turns occupancy peaks into recommended `PipelineConfig`
capacities under a zero-drop budget. Enable recording with
`PipelineConfig(telemetry=True)` — the default compiles the whole plane
away. `spans` times the host driver's layers on every launch, plane or
no plane, and writes them into the profiler trace beside the device ops
of the tick's `d3.*` named scopes.
"""
from repro.telemetry.trace import (TRACE_DEVICE_COLS, TRACE_HOST_COLS,
                                   TRACE_SCHEMA_VERSION, Trace,
                                   TraceRecorder, load_trace)
from repro.telemetry.spans import SpanClock, SpanStat
from repro.telemetry.advisor import (apply_recommendation, recommend,
                                     replay_ok)

__all__ = [
    "TRACE_DEVICE_COLS", "TRACE_HOST_COLS", "TRACE_SCHEMA_VERSION",
    "Trace", "TraceRecorder", "load_trace", "SpanClock", "SpanStat",
    "apply_recommendation", "recommend", "replay_ok",
]
