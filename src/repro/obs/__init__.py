"""Unified observability layer: metrics registry, request- and
dispatch-scoped tracing, and exporters (DESIGN.md §12).

Quickstart::

    from repro import obs

    reg = obs.registry()                       # process-wide registry
    log = obs.SpanLog("spans.jsonl")           # JSONL span sink
    eng = PolymulEngine(pl, span_log=log)      # traces every request
    ...
    log.flush()
    print(obs.to_prometheus(reg))              # scrape-ready text
    obs.conservation(obs.read_jsonl("spans.jsonl"))  # lifecycle audit

``python -m repro.launch.obs_report spans.jsonl`` renders the
latency/throughput/lifecycle report and runs the conservation gate
from the CLI.
"""
from repro.obs.export import parse_prometheus, to_json, to_prometheus
from repro.obs.metrics import (
    HIST_GROWTH,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_buckets,
    registry,
    reset_default_registry,
)
from repro.obs.tracing import (
    TERMINAL_STATUSES,
    Span,
    SpanLog,
    conservation,
    read_jsonl,
)

__all__ = [
    "HIST_GROWTH",
    "TERMINAL_STATUSES",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "Span",
    "SpanLog",
    "conservation",
    "default_buckets",
    "parse_prometheus",
    "read_jsonl",
    "registry",
    "reset_default_registry",
    "to_json",
    "to_prometheus",
]
