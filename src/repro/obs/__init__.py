"""Unified observability: span tracing, metrics registry, exporters.

One cross-cutting layer over the training transports and the serving
fleet (DESIGN.md §14):

  * ``obs.trace`` — a low-overhead, thread-safe span tracer.
    ``span("commit", worker=g)`` context managers nest naturally per
    thread, land in a process-wide ring buffer, and export as
    Chrome-trace JSON (``export_chrome``) so a whole async run or fleet
    sim loads in ``chrome://tracing`` / Perfetto.
  * ``obs.metrics`` — named counters / gauges / histograms with label
    sets behind one process-wide registry, plus bridges that absorb the
    pre-existing ad-hoc telemetry (transport ``wire_stats`` dicts,
    ``serve.metrics.ServingMetrics``) into the same schema
    (``repro_<layer>_<name>`` naming).
  * ``obs.export`` — Prometheus text format (optionally served by a tiny
    stdlib HTTP handler) and periodic JSONL snapshots.

Tracing is OFF by default and must stay nearly free when off: ``span``
costs one global flag check and a no-op context manager
(``benchmarks/bench_obs.py`` measures the bound CI enforces).  Metrics
are always recordable — the registry is just dicts behind a lock — but
nothing publishes into it unless an instrumented layer runs.

    from repro import obs

    obs.enable()
    ... run something instrumented ...
    obs.export_chrome("trace.json")       # load in chrome://tracing
    print(obs.to_prometheus())            # scrapeable text format
    obs.disable()
"""
from .trace import (  # noqa: F401
    Tracer,
    disable,
    enable,
    enabled,
    export_chrome,
    get_tracer,
    phase_breakdown,
    set_clock,
    span,
)
from .metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    publish_serving_metrics,
    publish_wire_stats,
)
from .export import (  # noqa: F401
    JsonlExporter,
    MetricsHTTPServer,
    to_prometheus,
)

__all__ = [
    "Tracer",
    "span",
    "enable",
    "disable",
    "enabled",
    "set_clock",
    "get_tracer",
    "export_chrome",
    "phase_breakdown",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "get_registry",
    "publish_wire_stats",
    "publish_serving_metrics",
    "to_prometheus",
    "MetricsHTTPServer",
    "JsonlExporter",
]
