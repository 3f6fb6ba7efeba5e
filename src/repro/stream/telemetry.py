"""Streaming telemetry hub (ISSUE 6 tentpole).

One :class:`StreamTelemetry` instance per detector (shared by all of its
stations) ties the observability primitives of ``repro.obsv`` to the
detection hot path:

* **in-dispatch counters** — every fused/unfused step returns the
  ``index.QC_FIELDS`` counter vector computed *inside* the already-traced
  program (no extra dispatch); ``record_step`` mirrors it into per-station
  registry counters (``step_<field>_total``). These are the device's own
  view of its guard activity, reconciled against the host-side quality
  dicts by the telemetry tests.
* **wall-time histograms** — the push wall (``chunk`` span), the device
  step seen from the host (``fused_step`` span) and the host tail
  (``host_tail`` span) land in log-bucketed histograms with per-station
  labels (the pooled dispatch is shared by all stations and is labeled
  ``station="pool"``); the engine hands in the span durations, so no
  interval is timed twice.
* **StepWatchdog** — the training loop's straggler/hang watchdog
  (``train/watchdog.py``) observes each ``fused_step`` duration;
  flagged steps increment ``straggler_steps_total`` and stay queryable
  via ``watchdog.events``.
* **span tracing** — a :class:`~repro.obsv.spans.SpanTracer` carries the
  span tree of every push (``chunk`` → ``ingest``/``dedup``,
  ``fused_step``/``put``/``dispatch``/``wait``/``pull``, ``host_tail``,
  ``detections``); each span is also a profiler annotation, and records
  (ids, parents, realtime ns) buffer in memory for an optional JSONL
  sink that serving turns on with a flag and flushes at each heartbeat;
  per-name totals feed ``metrics_snapshot``.
* **health surface** — ``heartbeat(det)`` builds the periodic liveness
  dict (real-time factor, throughput, drop-rate breakdown, quality
  counters) and ``prometheus(det)`` the text exposition, both consumed by
  ``serve_detect --metrics-every/--metrics-file``.
* **serving tier** (ISSUE 7) — ``ServeDetectEngine`` publishes through
  the same registry via the ``record_serve_*`` hooks: admission outcomes
  (``serve_requests_total{outcome=accepted|served|shed}``), per-tick
  queue-depth/slot-occupancy gauges, and the queue-wait/service/latency
  histogram split; ``serve_view()`` is the derived summary carried by
  the heartbeat and ``metrics_snapshot``.

The registry (and the watchdog's EMA) snapshot/restore alongside the
detector, so a restored service resumes its counters instead of zeroing
the dashboards.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.obsv.metrics import MetricsRegistry, merge_counts
from repro.obsv.spans import SpanTracer
from repro.stream.index import QC_FIELDS
from repro.train.watchdog import StepWatchdog, WatchdogConfig

METRICS_SCHEMA = "stream-metrics/v1"


class StreamTelemetry:
    def __init__(self, n_stations: int = 1, *,
                 registry: MetricsRegistry | None = None,
                 tracer: SpanTracer | None = None,
                 watchdog: StepWatchdog | None = None,
                 clock=time.perf_counter):
        self.n_stations = n_stations
        self.raw_walls: dict[str, list] | None = None
        self.registry = registry or MetricsRegistry()
        self.tracer = tracer or SpanTracer()
        if watchdog is None:
            watchdog = StepWatchdog(WatchdogConfig(hang_timeout_s=60.0),
                                    on_straggler=self._on_straggler)
        else:                       # chain the caller's policy with ours
            prev = watchdog.on_straggler
            watchdog.on_straggler = \
                lambda info: (prev(info), self._on_straggler(info))[0]
        self.watchdog = watchdog
        self.clock = clock
        self.t_start: float | None = None   # first chunk arrival
        # uptime carried over restores (wall time is not checkpointable)
        self._uptime_base = 0.0

    def _on_straggler(self, info: dict) -> None:
        self.registry.counter("straggler_steps_total").inc()

    # -- recording hooks (called from the engine hot path) -------------------

    def start(self) -> None:
        if self.t_start is None:
            self.t_start = self.clock()

    def uptime_s(self) -> float:
        if self.t_start is None:
            return self._uptime_base
        return self._uptime_base + (self.clock() - self.t_start)

    def record_chunk(self, station: int, wall_s: float, samples: int) -> None:
        s = str(station)
        self.registry.counter("chunks_total", station=s).inc()
        self.registry.counter("samples_total", station=s).inc(samples)
        self.registry.histogram("chunk_ingest_wall_seconds",
                                station=s).record(wall_s)

    def record_step(self, station: int, qc: np.ndarray) -> None:
        """Mirror one step's in-dispatch counter vector into the registry."""
        s = str(station)
        for name, v in zip(QC_FIELDS, np.asarray(qc).reshape(-1)):
            self.registry.counter(f"step_{name}_total", station=s).inc(int(v))

    def capture_raw_walls(self) -> dict[str, list]:
        """Opt in to exact wall-sample capture (bench_e2e).

        The registry histograms are log-bucketed — good enough for live
        health, but percentile() returns the bucket upper edge, which
        quantizes sub-2ms steps onto identical values. Benchmarks that
        publish percentiles call this once and compute them from the raw
        samples instead; the histogram-derived values stay available
        under separate keys for comparison."""
        if self.raw_walls is None:
            self.raw_walls = {"fused_step": [], "host_tail": []}
        return self.raw_walls

    def record_fused_wall(self, label: str, wall_s: float) -> None:
        """One device step seen from the host (the ``fused_step`` span):
        the histogram, and one step of the straggler watchdog."""
        self.watchdog.observe(wall_s)
        if self.raw_walls is not None:
            self.raw_walls["fused_step"].append(wall_s)
        self.registry.histogram("fused_step_wall_seconds",
                                station=label).record(wall_s)

    def record_host_tail(self, station: int, wall_s: float) -> None:
        if self.raw_walls is not None:
            self.raw_walls["host_tail"].append(wall_s)
        self.registry.histogram("host_tail_wall_seconds",
                                station=str(station)).record(wall_s)

    # -- location-tier hooks (ISSUE 9) ---------------------------------------

    def record_locate(self, groups: int, located: int, rejected: int,
                      wall: float) -> None:
        """One migration-stack pass over associated groups: how many went
        in, how many located detections came out, how many fell to the
        moveout-consistency gate, and the stack's wall time."""
        self.registry.counter("locate_passes_total").inc()
        self.registry.counter("locate_groups_total").inc(int(groups))
        self.registry.counter("located_detections_total").inc(int(located))
        self.registry.counter("moveout_rejected_total").inc(int(rejected))
        self.registry.histogram("locate_stack_wall_seconds").record(wall)

    def locate_view(self) -> dict:
        """Location-tier summary: stack passes, group flow, and the
        moveout-rejection count. All-zero without a locate tier."""
        reg = self.registry
        h = reg.histogram_merged("locate_stack_wall_seconds")
        return {
            "passes": int(reg.total("locate_passes_total")),
            "groups": int(reg.total("locate_groups_total")),
            "located": int(reg.total("located_detections_total")),
            "moveout_rejected": int(reg.total("moveout_rejected_total")),
            "stack_wall": {"count": h.count,
                           "p50_ms": round(h.percentile(0.50) * 1e3, 3),
                           "p95_ms": round(h.percentile(0.95) * 1e3, 3)},
        }

    # -- serving-tier hooks (called from ServeDetectEngine) ------------------

    def record_serve_admission(self, accepted: bool) -> None:
        """One admission decision: queued, or load-shed at the bound."""
        outcome = "accepted" if accepted else "shed"
        self.registry.counter("serve_requests_total", outcome=outcome).inc()
        if not accepted:
            self.registry.counter("serve_shed_total").inc()

    def record_serve_tick(self, active_slots: int, queue_depth: int) -> None:
        """One service tick: occupancy + backlog gauges, dispatch count
        (idle ticks — zero active slots — don't dispatch)."""
        self.registry.counter("serve_ticks_total").inc()
        if active_slots:
            self.registry.counter("serve_dispatches_total").inc()
            self.registry.counter("serve_slot_ticks_total").inc(active_slots)
        self.registry.gauge("serve_active_slots").set(active_slots)
        self.registry.gauge("serve_queue_depth").set(queue_depth)

    def record_serve_done(self, queue_wait_s: float, service_s: float,
                          latency_s: float) -> None:
        """One served request's arrival-time accounting: where the
        latency went (admission-queue wait vs. in-slot service)."""
        self.registry.counter("serve_requests_total", outcome="served").inc()
        self.registry.histogram("serve_queue_wait_seconds").record(
            queue_wait_s)
        self.registry.histogram("serve_service_seconds").record(service_s)
        self.registry.histogram("serve_latency_seconds").record(latency_s)

    def record_serve_refresh(self) -> None:
        self.registry.counter("serve_state_refreshes_total").inc()

    # -- derived views -------------------------------------------------------

    def drop_breakdown(self) -> dict:
        """Device-side step counters summed over stations (QC layout)."""
        return {name: int(self.registry.total(f"step_{name}_total"))
                for name in QC_FIELDS}

    def drop_rates(self) -> dict:
        """Per-guard drop rates relative to the raw pair/collision flow."""
        d = self.drop_breakdown()
        emitted = d["pairs_emitted"]
        denom = max(emitted + d["limited_pairs"], 1)
        raw = max(d["raw_collisions"], 1)
        return {
            "limited_pairs": round(d["limited_pairs"] / denom, 6),
            "quarantined_collisions":
                round(d["quarantined_collisions"] / raw, 6),
            "masked_fingerprints": round(
                d["masked_fingerprints"]
                / max(d["masked_fingerprints"] + emitted, 1), 6),
        }

    def serve_view(self) -> dict:
        """Serving-tier summary from the registry: admission outcomes,
        tick/dispatch counts, live occupancy gauges, and the (bucketed)
        latency split. All-zero when no serving engine shares this hub.
        """
        reg = self.registry

        def hist_ms(name):
            h = reg.histogram_merged(name)
            return {"count": h.count,
                    "p50_ms": round(h.percentile(0.50) * 1e3, 3),
                    "p95_ms": round(h.percentile(0.95) * 1e3, 3)}

        def tot(name, **labels):
            if labels:
                return int(reg.counter(name, **labels).value)
            return int(reg.total(name))

        return {
            "accepted": tot("serve_requests_total", outcome="accepted"),
            "served": tot("serve_requests_total", outcome="served"),
            "shed": tot("serve_requests_total", outcome="shed"),
            "ticks": tot("serve_ticks_total"),
            "dispatches": tot("serve_dispatches_total"),
            "slot_ticks": tot("serve_slot_ticks_total"),
            "refreshes": tot("serve_state_refreshes_total"),
            "queue_depth": int(reg.gauge("serve_queue_depth").value),
            "active_slots": int(reg.gauge("serve_active_slots").value),
            "latency": hist_ms("serve_latency_seconds"),
            "queue_wait": hist_ms("serve_queue_wait_seconds"),
            "service": hist_ms("serve_service_seconds"),
        }

    def stream_seconds(self, det) -> float:
        """Absolute-timeline seconds the detector has processed (the
        network ingests in lockstep — any station's sample count works)."""
        fs = det.cfg.fingerprint.fs
        if not det.stations:
            return 0.0
        return min(st.stats.samples for st in det.stations) / fs

    def real_time_factor(self, det) -> float:
        """Processed stream seconds per wall second since the first chunk
        (> 1 keeps up with real time; < 1 falls behind)."""
        wall = self.uptime_s()
        return self.stream_seconds(det) / max(wall, 1e-9)

    def heartbeat(self, det) -> dict:
        """The periodic liveness record ``serve_detect`` prints."""
        chunks = int(self.registry.total("chunks_total"))
        wall = self.uptime_s()
        return {
            "uptime_s": round(wall, 3),
            "stream_s": round(self.stream_seconds(det), 3),
            "rtf": round(self.real_time_factor(det), 3),
            "chunks": chunks,
            "pairs": int(self.registry.total("step_pairs_emitted_total")),
            "fp_per_s": [
                round(st.stats.fingerprints / max(wall, 1e-9), 1)
                for st in det.stations],
            "drop_rates": self.drop_rates(),
            "quality": det.quality_summary(),
            "serve": self.serve_view(),
            "stragglers": int(self.registry.total("straggler_steps_total")),
        }

    def heartbeat_line(self, det) -> str:
        return "HEARTBEAT " + json.dumps(self.heartbeat(det))

    def prometheus(self, det=None) -> str:
        """Text exposition of the registry, with point-in-time gauges
        (host_state_rows, rtf) and the host-side quality counters synced
        in first so the scrape is self-contained."""
        if det is not None:
            for i, st in enumerate(det.stations):
                self.registry.gauge("host_state_rows",
                                    station=str(i)).set(st.host_state_rows())
                for k, v in st.quality_summary().items():
                    self.registry.counter(f"quality_{k}_total",
                                          station=str(i)).set_total(int(v))
            self.registry.gauge("real_time_factor").set(
                self.real_time_factor(det))
            self.registry.gauge("uptime_seconds").set(self.uptime_s())
        return self.registry.render()

    def write_prometheus(self, path: str, det=None) -> None:
        tmp = str(path) + ".tmp"
        with open(tmp, "w") as fh:
            fh.write(self.prometheus(det))
        os.replace(tmp, path)

    # -- persistence ---------------------------------------------------------

    def snapshot(self) -> dict:
        return {
            "schema": "stream-telemetry/v1",
            "registry": self.registry.snapshot(),
            "uptime_s": self.uptime_s(),
            "watchdog": {"ema": self.watchdog.ema, "n": self.watchdog.n},
        }

    def restore(self, snap: dict) -> None:
        self.registry.restore(snap["registry"])
        self._uptime_base = float(snap.get("uptime_s", 0.0))
        self.t_start = None
        wd = snap.get("watchdog", {})
        self.watchdog.ema = wd.get("ema")
        self.watchdog.n = int(wd.get("n", 0))


def quality_view(ring_quality: dict, qc: dict) -> dict:
    """One station's quality summary: ingest reconciliation counters +
    in-dispatch guard counters, merged on the single shared aggregation
    path (``merge_counts``). Key set is the stable public contract."""
    return merge_counts([ring_quality, qc])


def metrics_snapshot(det) -> dict:
    """The single structured metrics view of a detector.

    Consumed by ``bench_stream`` / ``bench_e2e`` (the ``metrics`` section
    of their JSON artifacts), the examples, ``serve_detect``, and the
    tier-1 schema test — one shape for every dashboard.
    """
    tel = det.telemetry
    reg = tel.registry
    stream = merge_counts([st.stats.summary() for st in det.stations])
    # wall stats don't sum meaningfully across lockstep stations; report
    # the slowest station's view plus merged histograms below
    for k in ("wall_s", "chunk_ms_p50", "chunk_ms_p95", "chunks_per_s",
              "samples_per_s"):
        stream[k] = max(st.stats.summary()[k] for st in det.stations)
    return {
        "schema": METRICS_SCHEMA,
        "stations": len(det.stations),
        "uptime_s": round(tel.uptime_s(), 3),
        "stream_s": round(tel.stream_seconds(det), 3),
        "rtf": round(tel.real_time_factor(det), 3),
        "stream": stream,
        "per_station": [
            {"station": i, **st.stats.summary(),
             "host_state_rows": st.host_state_rows(),
             "quality": st.quality_summary()}
            for i, st in enumerate(det.stations)],
        "drops": tel.drop_breakdown(),
        "drop_rates": tel.drop_rates(),
        "quality": det.quality_summary(),
        "histograms": {
            name: reg.histogram_merged(name).summary()
            for name in ("chunk_ingest_wall_seconds",
                         "fused_step_wall_seconds",
                         "host_tail_wall_seconds",
                         "serve_latency_seconds",
                         "serve_queue_wait_seconds",
                         "locate_stack_wall_seconds")},
        "serve": tel.serve_view(),
        "locate": tel.locate_view(),
        "spans": tel.tracer.summary(),
        "watchdog": {"steps": tel.watchdog.n,
                     "stragglers": len(tel.watchdog.events)},
    }
