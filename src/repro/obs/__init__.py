"""repro.obs — unified metrics + tracing for the serve loop.

* :class:`MetricsRegistry` / :class:`Counter` / :class:`Gauge` /
  :class:`Histogram`: the namespaced instrument registry every stat
  surface (ingest, coalescer, tick, share, ckpt, mesh) reports into.
* :class:`Tracer`: host-side nested spans (ids, parents, monotonic ns,
  per-tick correlation ids), each a ``repro.*`` profiler annotation
  while open, written as JSONL on flush — strictly outside traced code.
* :func:`to_prometheus`: text exposition snapshot.
* :func:`summarize_trace`: the ``python -m repro.obs summarize`` CLI.

See README "Observability" for the metric-name reference table.
"""

from .export import to_prometheus
from .metrics import (
    DEFAULT_LATENCY_BUCKETS_MS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    percentile,
)
from .summarize import format_summary, summarize_trace
from .trace import NULL_SPAN, Span, Tracer, maybe_span, memory_tracer

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "percentile",
    "DEFAULT_LATENCY_BUCKETS_MS",
    "Tracer",
    "Span",
    "NULL_SPAN",
    "maybe_span",
    "memory_tracer",
    "to_prometheus",
    "summarize_trace",
    "format_summary",
]
