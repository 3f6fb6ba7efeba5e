"""Observability primitives (ISSUE 6).

Generic, dependency-light building blocks shared by the streaming
detector, the batch replay driver, and the serving loop:

``metrics``
    Host-side :class:`MetricsRegistry` — counters, gauges, and
    log-bucketed histograms with labels (``station="3"``), O(1) memory
    per metric, a JSON-able ``snapshot()``/``restore()`` pair (so they
    ride inside detector checkpoints), and a Prometheus text exposition
    (``render_prometheus``).

``spans``
    :class:`SpanTracer` — lightweight nested wall-clock spans (the
    streaming detector's tree per push: chunk → ingest/dedup →
    fused_step/put/dispatch/wait/pull → host_tail → detections) that
    always accumulate per-name totals, appear in a running
    ``jax.profiler`` trace as host annotations of the same names, and
    optionally buffer a structured JSONL log whose records carry ids,
    parents and realtime ns; plus an optional ``jax.profiler``
    trace-dump hook for when a heartbeat anomaly needs an XLA-level
    view.

What is *counted* where for the detection path (which counters come
from inside the fused dispatch vs. from the host) is documented in
``repro.stream`` ("observability path") — this package only provides
the containers.
"""
from repro.obsv.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                                merge_counts, render_prometheus)
from repro.obsv.spans import SpanTracer

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "merge_counts", "render_prometheus", "SpanTracer",
]
