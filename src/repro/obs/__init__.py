"""Observability: the flight recorder, exporters, metrics registry,
and the post-hoc overlap analyzer.

This package is the READ side of the serving stack: every subsystem
built in PRs 1-6 (runtime, transfer engine, admission controller,
device page pool, KV cache, server) emits typed ``TraceEvent``s into
one ``FlightRecorder`` per server, and everything here consumes that
stream — Perfetto traces (``export``), counters/gauges/histograms
(``metrics``), overlap-efficiency reports (``analyze``), and the
telemetry text renderer (``render``).  Nothing in ``repro.obs`` imports
from ``repro.serving`` (or any other repro subpackage): the emitters
depend on the recorder, never the other way around.
"""

from repro.obs.analyze import OverlapReport, OverlapRound, analyze
from repro.obs.clock import SYSTEM_CLOCK, EventClock, SystemClock
from repro.obs.export import to_jsonl, to_perfetto, write_jsonl, write_trace
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.recorder import (LEGACY_LABELS, NULL_SPAN, AdmissionEvent,
                                ChunkKVEvent, CounterSample, DecodeStep,
                                FlightRecorder, HostRequest, HostSpan,
                                KVEvent, PoolEvent, RequestEvent, SpanEvent,
                                TraceEvent, TransferRecord, WaveEvent)
from repro.obs.render import (render_replica_line, render_telemetry,
                              render_tenant_line)

__all__ = [
    "AdmissionEvent", "analyze", "ChunkKVEvent", "Counter", "CounterSample",
    "DecodeStep",
    "EventClock", "SYSTEM_CLOCK", "SystemClock",
    "FlightRecorder", "Gauge", "Histogram", "HostRequest", "HostSpan",
    "KVEvent", "LEGACY_LABELS", "NULL_SPAN",
    "MetricsRegistry", "OverlapReport", "OverlapRound", "PoolEvent",
    "RequestEvent", "render_replica_line", "render_telemetry",
    "render_tenant_line", "SpanEvent", "to_jsonl",
    "to_perfetto", "TraceEvent", "TransferRecord", "WaveEvent",
    "write_jsonl", "write_trace",
]
