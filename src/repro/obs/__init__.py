"""``repro.obs`` — dependency-free observability for the TACZ pipeline.

Three pieces, all stdlib-only:

  * :mod:`repro.obs.registry` — a thread-safe ``MetricsRegistry`` with
    counters, gauges, and fixed-bucket histograms, rendering Prometheus
    text exposition and estimating quantiles from the buckets.
  * :mod:`repro.obs.trace` — a ``Span``/``trace()`` context-manager API
    for nested per-stage timings, profiler annotations
    (``annotate``: ``layer.<component>.<stage>`` regions in a JAX device
    trace, opened only when jax is already imported and a profiler
    records), plus request IDs and the ``X-Repro-Request-Id`` header
    name.
  * :mod:`repro.obs.metrics` — the process-wide default ``REGISTRY``
    and the metric catalog every instrumented component records into.
  * :mod:`repro.obs.expo` — parser for the Prometheus text exposition;
    ``to_snapshot(parse(reg.render()))`` round-trips ``reg.snapshot()``
    exactly (property-tested), so anything that can scrape
    ``/v1/metrics`` can be programmatically read.
  * :mod:`repro.obs.collect` — ``FleetCollector``: polls N endpoints
    into ring-buffer time series, computes counter rates/deltas and
    windowed histogram quantiles across scrapes, aggregates per-shard
    series into fleet totals, and dumps JSON snapshots.
  * :mod:`repro.obs.slo` — declarative SLO rules (``p99 < 50ms``,
    ``error_rate < 0.1%``, …) with pending→firing→resolved alert state,
    evaluated against the collector and exported back as gauges.

See ``docs/observability.md`` for the full catalog, the tracing model,
and the SLO rule table.
"""
from . import metrics
from .collect import FleetCollector, Scrape
from .expo import ParsedFamily, ParsedHistogram
from .metrics import REGISTRY, is_enabled, set_enabled, timed
from .registry import (DEFAULT_TIME_BUCKETS, Counter, Gauge, Histogram,
                       MetricsRegistry, quantile_from_buckets)
from .slo import RULE_TYPES, SLOEngine, SLORule
from .trace import (REQUEST_ID_HEADER, Span, annotate, current_span,
                    new_request_id, root_span, trace)
from . import expo

__all__ = [
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "DEFAULT_TIME_BUCKETS", "quantile_from_buckets",
    "Span", "trace", "root_span", "current_span", "annotate",
    "new_request_id", "REQUEST_ID_HEADER",
    "REGISTRY", "metrics", "set_enabled", "is_enabled", "timed",
    "expo", "ParsedFamily", "ParsedHistogram",
    "FleetCollector", "Scrape",
    "SLOEngine", "SLORule", "RULE_TYPES",
]
