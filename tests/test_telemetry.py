"""Telemetry subsystem tests (ISSUE 6).

Pins the observability contract end to end:

  * ``repro.obsv`` primitives — registry counters/gauges/histograms,
    snapshot/restore, Prometheus text exposition (parsed back by the
    format guard), span tracer nesting + JSONL records;
  * telemetry on/off **bit-parity** — the in-dispatch counter vector is
    observation only: the same dirty trace streamed with
    ``telemetry=False`` yields the identical pair set and quality
    counters, with the counter tail compiled to zeros;
  * device-vs-host **reconciliation** — the device's own step counters
    (``step_<field>_total``) agree with the host-side accounting
    (``StreamStats.pairs``, ``quality_summary``) on dirty scenarios;
  * detector **snapshot/restore** carries the registry and watchdog EMA,
    so a restored service resumes its counters instead of zeroing;
  * ``metrics_snapshot`` schema (``stream-metrics/v1``) — the one
    structured view serve_detect / bench_stream / bench_e2e embed;
  * the ``StepWatchdog`` straggler path increments
    ``straggler_steps_total`` while still honoring a caller's callback.
"""
import dataclasses
import json
import math
import pathlib
import re
import sys

import numpy as np
import pytest

from repro.configs.fast_seismic import (smoke_config,
                                        stream_dirty_smoke_config)
from repro.core.synth import (ScenarioConfig, SynthConfig,
                              make_scenario_dataset)
from repro.obsv.metrics import (Histogram, MetricsRegistry, merge_counts,
                                render_prometheus)
from repro.obsv.spans import SpanTracer
from repro.stream import (METRICS_SCHEMA, QC_FIELDS, StreamingDetector,
                          metrics_snapshot)
from repro.stream.telemetry import StreamTelemetry
from repro.train.watchdog import StepWatchdog, WatchdogConfig

ROOT = str(pathlib.Path(__file__).parent.parent)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)             # the benchmarks package

from benchmarks.common import frozen_smoke_stats as _frozen  # noqa: E402


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------


def test_counter_labels_and_totals():
    reg = MetricsRegistry()
    reg.counter("pairs_total", station="0").inc(3)
    reg.counter("pairs_total", station="1").inc(4)
    reg.counter("pairs_total", station="0").inc()       # same instance
    assert reg.counter("pairs_total", station="0").value == 4
    assert reg.total("pairs_total") == 8
    assert reg.total("absent_total") == 0
    # set_total mirrors an external count and never goes backwards
    c = reg.counter("quality_gaps_total")
    c.set_total(7)
    c.set_total(5)
    assert c.value == 7
    # one name, one kind
    with pytest.raises(AssertionError):
        reg.gauge("pairs_total")


def test_gauge_point_in_time():
    reg = MetricsRegistry()
    g = reg.gauge("rtf")
    g.set(3)
    g.set(1.5)
    assert reg.gauge("rtf").value == 1.5


def test_histogram_buckets_summary_percentiles():
    h = Histogram()
    for v in [0.001] * 98 + [0.5, 1.0]:
        h.record(v)
    s = h.summary()
    assert s["count"] == 100
    assert s["sum"] == pytest.approx(98 * 0.001 + 1.5)
    assert s["min"] == 0.001 and s["max"] == 1.0
    # bucket-resolution percentiles: ≤ 2x overestimate, never below exact
    assert 0.001 <= s["p50"] <= 0.002
    assert 0.001 <= s["p95"] <= 0.002
    # values clamp to the edge buckets instead of erroring
    h.record(1e-12)
    h.record(1e9)
    assert h._bucket(1e-12) == 0
    assert h._bucket(1e9) == Histogram.N_BUCKETS - 1
    assert sum(h.counts) == h.count == 102
    # empty histogram summarizes to zeros, not inf
    assert Histogram().summary() == {"count": 0, "sum": 0.0, "min": 0.0,
                                     "max": 0.0, "p50": 0.0, "p95": 0.0}


def test_histogram_merged_across_labels():
    reg = MetricsRegistry()
    reg.histogram("wall_seconds", station="0").record(0.01)
    reg.histogram("wall_seconds", station="1").record(0.04)
    m = reg.histogram_merged("wall_seconds")
    assert m.count == 2
    assert m.total == pytest.approx(0.05)
    assert m.vmin == 0.01 and m.vmax == 0.04


def test_merge_counts_sums_and_order():
    out = merge_counts([{"a": 1, "b": 2}, {"b": 3, "c": 4}])
    assert out == {"a": 1, "b": 5, "c": 4}
    assert list(out) == ["a", "b", "c"]      # first-seen key order


def test_registry_snapshot_restore_roundtrip():
    reg = MetricsRegistry()
    reg.counter("pairs_total", station="0").inc(12)
    reg.gauge("rtf").set(7.5)
    reg.histogram("wall_seconds", station="0").record(0.02)
    reg.histogram("empty_seconds")           # registered but never recorded
    snap = reg.snapshot()
    assert snap["schema"] == "metrics/v1"
    json.dumps(snap)                         # JSON-able (rides checkpoints)
    reg2 = MetricsRegistry()
    reg2.restore(snap)
    assert reg2.snapshot() == snap
    assert reg2.render() == reg.render()
    h = reg2.histogram("empty_seconds")
    assert h.count == 0 and h.vmin == math.inf


# ---------------------------------------------------------------------------
# Prometheus exposition format guard
# ---------------------------------------------------------------------------

_LINE = re.compile(r"^([a-zA-Z_][a-zA-Z0-9_]*)"
                   r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""
                   r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})? (\S+)$")


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("pairs_total", station="0").inc(5)
    reg.gauge("rtf").set(2.25)
    h = reg.histogram("wall_seconds", station="0")
    for v in (0.001, 0.002, 0.004, 1.0):
        h.record(v)
    text = render_prometheus(reg, namespace="repro")
    lines = text.strip().split("\n")
    # one TYPE comment per metric family, kinds as registered
    types = {m.group(1): m.group(2) for ln in lines
             if (m := re.match(r"# TYPE (\S+) (\S+)$", ln))}
    assert types == {"repro_pairs_total": "counter", "repro_rtf": "gauge",
                     "repro_wall_seconds": "histogram"}
    samples = [ln for ln in lines if not ln.startswith("#")]
    parsed = {}
    for ln in samples:
        m = _LINE.match(ln)
        assert m, f"unparseable exposition line: {ln!r}"
        float(m.group(4))                    # value is numeric
        parsed[m.group(1) + (m.group(2) or "")] = float(m.group(4))
    assert parsed['repro_pairs_total{station="0"}'] == 5
    assert parsed["repro_rtf"] == 2.25
    # histogram: cumulative non-decreasing buckets, +Inf == _count
    buckets = [(ln, float(_LINE.match(ln).group(4))) for ln in samples
               if ln.startswith("repro_wall_seconds_bucket")]
    counts = [v for _, v in buckets]
    assert counts == sorted(counts)
    assert '+Inf' in buckets[-1][0]
    assert buckets[-1][1] == 4
    assert parsed['repro_wall_seconds_count{station="0"}'] == 4
    assert parsed['repro_wall_seconds_sum{station="0"}'] == \
        pytest.approx(1.007)


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_span_nesting_totals_and_jsonl(tmp_path):
    clk = _FakeClock()
    path = tmp_path / "spans.jsonl"
    tr = SpanTracer(jsonl_path=str(path), clock=clk)
    with tr.span("outer", station=0):
        clk.t += 1.0
        with tr.span("inner"):
            clk.t += 0.25
    with tr.span("inner"):
        clk.t += 0.25
    tr.close()
    assert tr.total_s("outer") == pytest.approx(1.25)
    assert tr.total_s("inner") == pytest.approx(0.5)
    assert tr.total_s("absent") == 0.0
    assert tr.summary() == {
        "outer": {"count": 1, "total_s": pytest.approx(1.25)},
        "inner": {"count": 2, "total_s": pytest.approx(0.5)}}
    recs = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(recs) == 3                    # exit order: inner, outer, inner
    assert recs[0]["path"] == "outer/inner" and recs[0]["depth"] == 1
    assert recs[1]["path"] == "outer" and recs[1]["depth"] == 0
    assert recs[1]["station"] == 0           # span attrs ride the record
    assert recs[2]["path"] == "inner" and recs[2]["depth"] == 0
    assert all(r["dur_s"] >= 0 and "ts" in r for r in recs)


def test_span_records_ids_parents_and_buffered_sink(tmp_path):
    """Records carry id / parent / trace and realtime start/end; nothing
    is written before flush(), and a full buffer is written as the next
    root span opens."""
    clk = _FakeClock()
    path = tmp_path / "spans.jsonl"
    tr = SpanTracer(jsonl_path=str(path), clock=clk)
    tr.buffer_spans = 3
    with tr.span("chunk") as root:
        clk.t += 1.0
        with tr.span("ingest") as ing:
            with tr.span("dedup", station="pool") as dd:
                clk.t += 0.5
                dd.set(flagged=2)
    assert (root.dur_s, ing.dur_s, dd.dur_s) == (1.5, 0.5, 0.5)
    assert not path.exists()                 # buffered, not written
    with tr.span("chunk"):                   # the 4th opens: 3 written
        clk.t += 0.25
    recs = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [r["name"] for r in recs] == ["dedup", "ingest", "chunk"]
    dedup, ingest, chunk = recs
    assert chunk["parent"] is None and chunk["trace"] == chunk["id"]
    assert ingest["parent"] == chunk["id"] == ingest["trace"]
    assert dedup["parent"] == ingest["id"] and dedup["trace"] == chunk["id"]
    assert dedup["flagged"] == 2 and dedup["station"] == "pool"
    assert dedup["path"] == "chunk/ingest/dedup" and dedup["depth"] == 2
    assert [r["dur_s"] for r in recs] == [0.5, 0.5, 1.5]
    # realtime stamps: nested intervals, ``ts`` their end in seconds
    assert (chunk["start_ns"] <= ingest["start_ns"] <= dedup["start_ns"]
            <= dedup["end_ns"] <= ingest["end_ns"] <= chunk["end_ns"])
    assert all(r["ts"] == pytest.approx(r["end_ns"] * 1e-9) for r in recs)
    tr.flush()
    recs = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(recs) == 4
    assert recs[3]["parent"] is None and recs[3]["trace"] == recs[3]["id"]
    assert len({r["id"] for r in recs}) == 4
    assert tr.totals["chunk"] == [2, pytest.approx(1.75)]


def test_spans_on_the_profiler_clock(tmp_path):
    """Every span is a host event of its name in a profiler trace, and
    its record's [start_ns, end_ns] lies inside that event once the
    profile's ``profile_start_time`` is added."""
    import glob
    import time

    import jax
    from jax.profiler import ProfileData
    path = tmp_path / "spans.jsonl"
    tr = SpanTracer(jsonl_path=str(path))
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        for _ in range(2):
            with tr.span("chunk"):
                with tr.span("ingest"):
                    with tr.span("dedup"):
                        time.sleep(0.002)
                with tr.span("fused_step"):
                    with tr.span("pull"):
                        time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    tr.close()
    recs = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(recs) == 10
    (xplane,) = glob.glob(str(tmp_path / "prof" / "plugins" / "profile"
                              / "*" / "*.xplane.pb"))
    pd = ProfileData.from_file(xplane)
    start = None
    events: dict = {}
    for plane in pd.planes:
        if plane.name == "Task Environment":
            start = int(dict(plane.stats)["profile_start_time"])
    assert start is not None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                a = start + int(ev.start_ns)
                events.setdefault(ev.name, []).append(
                    (a, a + int(ev.duration_ns)))
    for r in recs:
        assert any(a <= r["start_ns"] and r["end_ns"] <= b
                   for a, b in events.get(r["name"], ())), r


# span tree of a push: each name and the name of its parent
_TREE = {"ingest": "chunk", "dedup": "ingest", "fused_step": "chunk",
         "put": "fused_step", "dispatch": "fused_step",
         "wait": "fused_step", "pull": "fused_step",
         "host_tail": "chunk", "detections": "chunk"}


def _check_span_tree(path, expect: set) -> None:
    recs = [json.loads(ln) for ln in path.read_text().splitlines()]
    by_id = {r["id"]: r for r in recs}
    kids: dict = {}
    for r in recs:
        kids.setdefault(r["parent"], []).append(r)
    assert {r["name"] for r in recs} == expect | {"chunk"}
    for r in recs:
        if r["name"] == "chunk":
            assert r["parent"] is None and r["trace"] == r["id"]
            continue
        up = by_id[r["parent"]]
        assert up["name"] == _TREE[r["name"]], (r, up)
        assert r["trace"] == by_id[r["trace"]]["id"]
        assert by_id[r["trace"]]["name"] == "chunk"
        assert up["start_ns"] <= r["start_ns"] <= r["end_ns"] <= up["end_ns"]
    for r in recs:
        if r["name"] in ("chunk", "fused_step"):
            inner = sum(k["dur_s"] for k in kids.get(r["id"], ()))
            assert r["dur_s"] >= inner, r
        if r["name"] == "fused_step":
            assert sorted(k["name"] for k in kids[r["id"]]) == \
                ["dispatch", "pull", "put", "wait"]
        if r["name"] in ("put", "pull"):
            assert r["bytes"] > 0
    assert all("flagged" in r for r in recs if r["name"] == "dedup")
    assert all("pairs" in r for r in recs if r["name"] == "host_tail")


def _tree_config():
    from repro.configs.fast_seismic import stream_bounded_smoke_config
    return dataclasses.replace(stream_bounded_smoke_config(),
                               dup_window_fingerprints=512)


def test_push_span_tree_pooled_and_solo(tmp_path):
    """A pooled StreamingDetector and a solo StationStream each give one
    span tree per push: ingest (with dedup), the step's put / dispatch /
    wait / pull, the host tail, and (pooled) the detections poll."""
    from repro.core.synth import make_dataset
    from repro.stream.engine import StationStream
    cfg = smoke_config()
    scfg = _tree_config()
    ds = make_dataset(_base_synth(n_stations=2, duration_s=300.0))
    wf = np.asarray(ds.waveforms, np.float32)
    med_mad = _frozen(cfg, wf[0])

    det = StreamingDetector(cfg, scfg, n_stations=2, med_mad=med_mad)
    assert det.pooled and det.rolling
    det.telemetry.tracer = SpanTracer(jsonl_path=str(tmp_path / "p.jsonl"))
    for chunk in np.array_split(wf, 6, axis=1):
        det.push(chunk)
    det.telemetry.tracer.close()
    _check_span_tree(tmp_path / "p.jsonl", set(_TREE))
    # the registry histograms and the watchdog read the span durations
    tot = det.telemetry.tracer.totals
    h = det.telemetry.registry.histogram_merged("fused_step_wall_seconds")
    assert h.count == det.telemetry.watchdog.n == tot["fused_step"][0] > 0
    assert det.stations[0].stats.chunks == tot["chunk"][0] == 6
    assert det.stations[0].stats.wall_total_s == \
        pytest.approx(tot["chunk"][1])

    st = StationStream(cfg, scfg, med_mad=med_mad)
    st.telemetry.tracer = SpanTracer(jsonl_path=str(tmp_path / "s.jsonl"))
    for chunk in np.array_split(wf[0], 6):
        st.push(chunk)
    st.telemetry.tracer.close()
    _check_span_tree(tmp_path / "s.jsonl", set(_TREE) - {"detections"})


def test_pool_step_lowering_carries_stage_scopes():
    """Every stage of the pool step runs under its named scope, which the
    lowered program's locations carry."""
    from repro.configs.fast_seismic import stream_compact_smoke_config
    from repro.stream import fused
    from repro.stream.index import StreamIndexConfig
    cfg = smoke_config()
    scfg = dataclasses.replace(
        stream_compact_smoke_config(), window_fingerprints=2048,
        saturation_limit=10, dup_sig_tables=2, occ_limit=30,
        index=StreamIndexConfig(n_buckets=2048, bucket_cap=8,
                                pk_slots=4096, occ_slots=4096))
    wave = np.zeros((2, 4096), np.float32)
    det = StreamingDetector(cfg, scfg, n_stations=2,
                            med_mad=_frozen(cfg, wave[0]))
    adv = np.zeros((2, det.stations[0].ring.advance), np.float32)
    lowered = fused.pool_step_advance.lower(
        det.pstate, adv, det._pool_mappings, np.int32(0), cfg.fingerprint,
        cfg.lsh, scfg.window_fingerprints, scfg.saturation_limit,
        scfg.dup_sig_tables, scfg.occ_limit, 1, scfg.max_pairs_per_block,
        scfg.verify_code, scfg.verify_min_jaccard)
    text = lowered.as_text(debug_info=True)
    for scope in ("fingerprint", "hash", "expire", "dup_guard", "insert",
                  "query", "limit", "compact", "verify"):
        # a scope under vmap reads ``vmap(<scope>)``
        assert re.search(rf'"jit\(pool_step_advance\)[^"]*[/(]{scope}[)/]',
                         text), scope


# ---------------------------------------------------------------------------
# watchdog integration
# ---------------------------------------------------------------------------


def test_watchdog_observe_flags_the_same_straggler():
    """``observe(dt)`` (the detector hands in its span's duration) flags
    exactly what ``step_start``/``step_end`` flag for the same steps."""
    steps = [0.1] * 6 + [0.5] + [0.1] * 3 + [400.0]
    clk = _FakeClock()
    timed = StepWatchdog(WatchdogConfig(min_samples=2, straggler_factor=2.0,
                                        hang_timeout_s=300.0), clock=clk)
    told = StepWatchdog(WatchdogConfig(min_samples=2, straggler_factor=2.0,
                                       hang_timeout_s=300.0))
    for dt in steps:
        timed.step_start()
        clk.t += dt
        assert timed.step_end() == pytest.approx(dt)
        assert told.observe(dt) == dt
    assert [e["reason"] for e in told.events] == ["straggler", "hang"]
    assert [(e["step"], e["reason"]) for e in told.events] == \
        [(e["step"], e["reason"]) for e in timed.events]
    assert told.ema == pytest.approx(timed.ema) and told.n == timed.n


def test_watchdog_straggler_counts_and_callback_chain():
    clk = _FakeClock()
    seen = []
    wd = StepWatchdog(WatchdogConfig(min_samples=2, straggler_factor=2.0,
                                     hang_timeout_s=1000.0),
                      on_straggler=seen.append, clock=clk)
    tel = StreamTelemetry(1, watchdog=wd)    # chains, never replaces
    for _ in range(5):                       # EMA settles at 0.1 s
        wd.step_start()
        clk.t += 0.1
        wd.step_end()
    assert tel.registry.total("straggler_steps_total") == 0
    wd.step_start()
    clk.t += 5.0                             # 50× EMA, below hang timeout
    wd.step_end()
    assert tel.registry.total("straggler_steps_total") == 1
    assert len(seen) == 1                    # caller's policy still fired
    assert seen[0]["reason"] == "straggler"
    assert wd.events == seen


# ---------------------------------------------------------------------------
# streaming integration (dirty scenarios)
# ---------------------------------------------------------------------------


def _raw_pairs(st):
    tri = (np.concatenate(st.triplets, axis=0) if st.triplets
           else np.zeros((0, 3), np.int64))
    return set(zip(tri[:, 0].tolist(), tri[:, 1].tolist()))


def _stream(cfg, scfg, wf, med_mad, n_stations=1, n_chunks=10):
    det = StreamingDetector(cfg, scfg, n_stations=n_stations,
                            med_mad=med_mad)
    wf = np.atleast_2d(np.asarray(wf, np.float32))
    for chunk in np.array_split(wf, n_chunks, axis=1):
        det.push(chunk if n_stations > 1 else chunk[0])
    det.flush()
    return [_raw_pairs(st) for st in det.stations], det


def _base_synth(**over):
    kw = dict(duration_s=600.0, n_stations=1, n_sources=2,
              events_per_source=5, event_snr=3.0, seed=3)
    kw.update(over)
    return SynthConfig(**kw)


def _dirty_scenario(**over):
    kw = dict(base=_base_synth(), n_gaps=2, gap_dur_s=(2.0, 5.0),
              glitch_stations=(0,), glitch_trains=1,
              glitch_train_dur_s=150.0, seed=1)
    kw.update(over)
    return make_scenario_dataset(ScenarioConfig(**kw))


def test_telemetry_off_bit_parity_on_dirty_trace():
    """The counter tail is observation only: telemetry=False compiles it
    away and the detections — pair set AND host quality counters — are
    bit-identical on a gap+glitch trace."""
    cfg = smoke_config()
    scfg_on = stream_dirty_smoke_config()
    assert scfg_on.telemetry                 # the production default
    scfg_off = dataclasses.replace(scfg_on, telemetry=False)
    scen = _dirty_scenario()
    med_mad = _frozen(cfg, scen.clean.waveforms[0])
    (on,), det_on = _stream(cfg, scfg_on, scen.waveforms[0], med_mad)
    (off,), det_off = _stream(cfg, scfg_off, scen.waveforms[0], med_mad)
    assert on == off
    assert det_on.quality_summary() == det_off.quality_summary()
    # with telemetry on, the full counter vector is live…
    d_on = det_on.telemetry.drop_breakdown()
    assert d_on["pairs_emitted"] > 0
    assert d_on["masked_fingerprints"] > 0   # the gaps
    assert d_on["raw_collisions"] >= d_on["pairs_emitted"]
    # …with it off, the telemetry tail constant-folds to zero while the
    # always-on guard fields keep counting
    d_off = det_off.telemetry.drop_breakdown()
    for name in ("pairs_emitted", "masked_fingerprints", "raw_collisions",
                 "quarantined_collisions"):
        assert d_off[name] == 0
    for name in ("duplicate_fingerprints", "saturated_lookups",
                 "limited_pairs"):
        assert d_off[name] == d_on[name]


def test_device_host_counter_reconciliation_pooled():
    """The device's in-dispatch counters and the host-side accounting are
    two independent views of the same stream — they must agree, per
    station, on a dirty pooled run."""
    cfg = smoke_config()
    scfg = stream_dirty_smoke_config()
    scen = _dirty_scenario(base=_base_synth(n_stations=2),
                           glitch_stations=(1,))
    med_mad = _frozen(cfg, scen.clean.waveforms[0])
    _, det = _stream(cfg, scfg, scen.waveforms, med_mad, n_stations=2)
    assert det.pooled
    reg = det.telemetry.registry
    drops = det.telemetry.drop_breakdown()
    # device pairs_emitted == host StreamStats.pairs, station by station
    for i, st in enumerate(det.stations):
        dev = reg.counter("step_pairs_emitted_total", station=str(i)).value
        assert dev == st.stats.pairs
    assert drops["pairs_emitted"] == sum(st.stats.pairs
                                         for st in det.stations)
    # guard fields whose only source is the device vector surface
    # identically in quality_summary…
    q = det.quality_summary()
    for name in ("saturated_lookups", "limited_pairs"):
        assert drops[name] == q[name]
    # …while duplicate_fingerprints also absorbs the host-side
    # sample-exact guard, so the device view is a lower bound
    assert drops["duplicate_fingerprints"] <= q["duplicate_fingerprints"]
    assert drops["pairs_emitted"] > 0
    assert drops["masked_fingerprints"] > 0  # the gaps masked in-dispatch
    # rates are consistent with the breakdown they summarize
    rates = det.telemetry.drop_rates()
    denom = drops["pairs_emitted"] + drops["limited_pairs"]
    assert rates["limited_pairs"] == \
        pytest.approx(drops["limited_pairs"] / denom, abs=1e-6)
    assert 0.0 <= rates["masked_fingerprints"] <= 1.0


def test_detector_snapshot_restores_telemetry(tmp_path):
    """A restored detector resumes its counters (and the watchdog EMA)
    instead of zeroing the dashboards, and keeps counting on top."""
    cfg = smoke_config()
    scfg = stream_dirty_smoke_config()
    scen = _dirty_scenario()
    med_mad = _frozen(cfg, scen.clean.waveforms[0])
    wf = np.atleast_2d(scen.waveforms[0])
    chunks = np.array_split(wf, 10, axis=1)
    det = StreamingDetector(cfg, scfg, n_stations=1, med_mad=med_mad)
    for c in chunks[:6]:
        det.push(c[0])
    drops_mid = det.telemetry.drop_breakdown()
    wd_mid = (det.telemetry.watchdog.ema, det.telemetry.watchdog.n)
    det.snapshot(str(tmp_path))
    det2, _ = StreamingDetector.restore(str(tmp_path), cfg, scfg)
    assert det2.telemetry.drop_breakdown() == drops_mid
    assert (det2.telemetry.watchdog.ema, det2.telemetry.watchdog.n) == wd_mid
    assert det2.telemetry.uptime_s() > 0     # uptime carries over
    for c in chunks[6:]:                     # counters keep growing
        det2.push(c[0])
    det2.flush()
    drops_end = det2.telemetry.drop_breakdown()
    assert drops_end["pairs_emitted"] >= drops_mid["pairs_emitted"]
    assert drops_end["pairs_emitted"] == det2.stations[0].stats.pairs


def test_metrics_snapshot_schema_and_prometheus_surface():
    """``metrics_snapshot`` is the one structured view every consumer
    (serve_detect, bench_stream, bench_e2e, examples) embeds — pin its
    shape; and the Prometheus surface scrapes the same registry."""
    cfg = smoke_config()
    scfg = stream_dirty_smoke_config()
    scen = _dirty_scenario()
    med_mad = _frozen(cfg, scen.clean.waveforms[0])
    _, det = _stream(cfg, scfg, scen.waveforms[0], med_mad)
    m = det.metrics_snapshot()
    m2 = metrics_snapshot(det)               # the method is the function
    wall_keys = ("uptime_s", "rtf")          # live clock: not comparable
    assert {k: v for k, v in m.items() if k not in wall_keys} == \
        {k: v for k, v in m2.items() if k not in wall_keys}
    json.dumps(m)                            # artifact-ready
    assert m["schema"] == METRICS_SCHEMA == "stream-metrics/v1"
    assert set(m) == {"schema", "stations", "uptime_s", "stream_s", "rtf",
                      "stream", "per_station", "drops", "drop_rates",
                      "quality", "histograms", "serve", "locate", "spans",
                      "watchdog"}
    assert m["stations"] == 1
    assert set(m["drops"]) == set(QC_FIELDS)
    assert m["quality"] == det.quality_summary()
    assert len(m["per_station"]) == 1
    ps = m["per_station"][0]
    assert ps["station"] == 0 and "host_state_rows" in ps
    assert set(m["histograms"]) == {"chunk_ingest_wall_seconds",
                                    "fused_step_wall_seconds",
                                    "host_tail_wall_seconds",
                                    "serve_latency_seconds",
                                    "serve_queue_wait_seconds",
                                    "locate_stack_wall_seconds"}
    # no serving engine shares this detector's hub → all-zero serve view
    assert m["serve"]["served"] == 0 and m["serve"]["shed"] == 0
    # no locate tier on this detector → all-zero locate view
    assert m["locate"]["passes"] == 0 and m["locate"]["located"] == 0
    assert m["histograms"]["fused_step_wall_seconds"]["count"] == \
        m["watchdog"]["steps"] > 0
    for name in ("ingest", "fused_step", "host_tail"):
        assert m["spans"][name]["count"] > 0
    assert m["stream"]["pairs"] == m["drops"]["pairs_emitted"]
    # the scrape carries the same registry plus point-in-time gauges and
    # the host quality counters, every line parseable
    text = det.telemetry.prometheus(det)
    for ln in text.strip().split("\n"):
        assert ln.startswith("# TYPE ") or _LINE.match(ln), ln
    assert 'repro_step_pairs_emitted_total{station="0"} ' \
        f'{m["drops"]["pairs_emitted"]}' in text
    assert "# TYPE repro_real_time_factor gauge" in text
    assert 'repro_quality_suppressed_fingerprints_total{station="0"}' in text
    assert 'repro_host_state_rows{station="0"}' in text
