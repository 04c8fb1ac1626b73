"""repro.obs — tracing, metrics, and per-stage profiling.

One timing idiom for the whole repo:

* ``with obs.timed("fl.round") as sw: ...`` — always-on stopwatch
  (replaces raw ``time.perf_counter()`` pairs); ``sw.dur_s`` after.
* ``with obs.span("engine.select"): ...`` — a host phase.  It never
  fences device work; it lands on the profiler's host plane
  (``jax.profiler.TraceAnnotation``) and in the open per-call record,
  and as a Chrome trace event when tracing is enabled
  (``obs.set_tracer(obs.Tracer())``).
* ``with obs.record("fednc.round"): ...`` — a per-call record of an
  entry point: span nanoseconds and counts summed by name, kept in a
  bounded ring (``obs.last_records(name, n)``) whether or not tracing
  is enabled.
* ``reg = obs.MetricsRegistry(); reg.counter("dispatches").inc()`` —
  mergeable counters/gauges/histograms snapshotting to
  ``fednc-metrics-v1`` JSON (increments also count in the open
  record).

``python -m repro.obs TRACE_serve.json`` summarizes saved traces;
see docs/observability.md.
"""
from repro.obs.metrics import (
    METRICS_SCHEMA,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    exp_buckets,
    merge_snapshots,
)
from repro.obs.ring import (
    RECORDS_MAX,
    Record,
    count,
    last_records,
    record,
    records,
    sample,
)
from repro.obs.report import (
    load_trace,
    markdown_summary,
    merge_events,
    stage_totals,
    summarize,
    validate_trace,
)
from repro.obs.trace import (
    NULL_TRACER,
    TRACE_SCHEMA,
    NullTracer,
    Tracer,
    clock,
    device_sync,
    events_document,
    get_tracer,
    save_events,
    set_tracer,
    span,
    timed,
)

__all__ = [
    "METRICS_SCHEMA", "TRACE_SCHEMA",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "exp_buckets", "merge_snapshots",
    "load_trace", "markdown_summary", "merge_events", "stage_totals",
    "summarize", "validate_trace",
    "NULL_TRACER", "NullTracer", "Tracer", "clock", "device_sync",
    "events_document", "get_tracer", "save_events", "set_tracer",
    "span", "timed",
    "RECORDS_MAX", "Record", "count", "last_records", "record",
    "records", "sample",
]
