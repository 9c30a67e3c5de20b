"""repro.obs — unified runtime observability (tracing, metrics, export).

The package ROOT is dependency-free (stdlib only — producers include the
deliberately-jax-free ``repro.dist.fault`` and the numpy-only benches):

* ``tracing``        — ``Tracer.span("device_step")`` host-side spans,
                       also opened as ``jax.profiler.TraceAnnotation``s
                       once jax is loaded, instants, and ``counter``
                       gauge samples;
                       ``trace_export.write_chrome_trace`` emits
                       Perfetto-loadable Chrome-trace JSON ('X'/'i'/'C').
* ``metrics``        — typed ``Counter``/``Gauge``/``Histogram`` (fixed
                       log-spaced buckets: p50/p99 from merges, not stored
                       samples) + fixed-size ``VectorCounter``/
                       ``VectorGauge`` per-bank series behind a
                       ``MetricRegistry``; plus ``empirical_percentile``,
                       the ONE home of the sorted-index percentile
                       convention the latency reports and committed
                       benches share.
* ``metrics_export`` — JSON snapshots (schema-stable: CI gates on the
                       key-path set), Prometheus text exposition (vector
                       metrics as labeled series), periodic writer, and
                       the CLIs' one-line machine summary.

Two submodules are NOT re-exported here, by design — import them directly:

* ``repro.obs.traffic`` — measured per-bank read/byte counters computed
  on-device inside the jit'd step (imports jax) + numpy recount twins and
  the ``TrafficAccumulator`` registry bridge.
* ``repro.obs.slo``     — the rolling-window SLO watchdog (numpy +
  ``repro.core.hwmodel``): modeled-vs-measured breach detection feeding
  the Replanner's bank-cost penalty hook.

See README.md §Observability for the CLI flags (``--trace-out``,
``--metrics-out``, ``--metrics-every``, ``--slo-p99-us``) and the
metric-name glossary.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricRegistry,
                               DEFAULT_BUCKETS, VectorCounter, VectorGauge,
                               empirical_p50, empirical_p99,
                               empirical_percentile, log_bucket_bounds)
from repro.obs.metrics_export import (PeriodicMetricsWriter, prometheus_text,
                                      snapshot_doc, summary_dict,
                                      summary_line, write_metrics_json)
from repro.obs.trace_export import chrome_trace_events, write_chrome_trace
from repro.obs.tracing import NULL_TRACER, Tracer

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricRegistry", "DEFAULT_BUCKETS",
    "VectorCounter", "VectorGauge",
    "empirical_p50", "empirical_p99", "empirical_percentile",
    "log_bucket_bounds",
    "PeriodicMetricsWriter", "prometheus_text", "snapshot_doc",
    "summary_dict", "summary_line", "write_metrics_json",
    "chrome_trace_events", "write_chrome_trace",
    "NULL_TRACER", "Tracer",
]
