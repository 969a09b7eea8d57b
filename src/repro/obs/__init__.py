"""Observability layer: device-side traversal counters, host metrics,
Prometheus/JSON export and profiling spans.

The layer every serving surface reports through (see
``docs/OBSERVABILITY.md`` for the metric catalog):

  * ``repro.obs.metrics`` — counters / gauges / fixed-bucket histograms
    with p50/p90/p99 summaries, one process-default registry;
  * ``repro.obs.stats`` — the always-on loop totals every jitted search
    core returns and the ``SearchStats`` pytree it optionally emits
    (``stats=True``), plus the host-side bridges (``record_loop_totals``,
    ``record_search_stats``) into the registry;
  * ``repro.obs.export`` — Prometheus text exposition, JSON snapshots,
    file writers and a daemon-thread HTTP endpoint;
  * ``repro.obs.trace`` — ``trace_span`` / ``capture_trace`` profiling
    hooks that use ``jax.profiler`` when available and degrade to timed
    spans otherwise.

``repro.obs`` sits below every serving layer: it imports only
jax/numpy/stdlib, so kernels-adjacent code can depend on it freely.
"""
from repro.obs.export import (
    MetricsServer,
    json_snapshot,
    parse_prometheus_text,
    start_metrics_server,
    to_json,
    to_prometheus_text,
    write_json,
    write_prometheus,
)
from repro.obs.metrics import (
    COUNT_BUCKETS,
    FRACTION_BUCKETS,
    LATENCY_BUCKETS_S,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    resolve,
)
from repro.obs.stats import (
    SearchStats,
    combine_stats,
    init_search_stats,
    per_query_dict,
    record_loop_totals,
    record_search_stats,
    stats_to_host,
)
from repro.obs.trace import capture_trace, trace_span

__all__ = [
    "COUNT_BUCKETS",
    "FRACTION_BUCKETS",
    "LATENCY_BUCKETS_S",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "MetricsServer",
    "SearchStats",
    "capture_trace",
    "combine_stats",
    "get_registry",
    "init_search_stats",
    "json_snapshot",
    "parse_prometheus_text",
    "per_query_dict",
    "record_loop_totals",
    "record_search_stats",
    "resolve",
    "start_metrics_server",
    "stats_to_host",
    "to_json",
    "to_prometheus_text",
    "trace_span",
    "write_json",
    "write_prometheus",
]
