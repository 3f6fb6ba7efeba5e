"""End-to-end streaming hot-path benchmark (ISSUE 3): BENCH_e2e.json.

Measures the fused single-dispatch chunk step against the unfused
pipeline at the real-time **latency configuration**
(``configs.fast_seismic.latency_config``: short blocks for low alert
latency — the regime where per-stage dispatch overhead, not FLOPs, bounds
throughput), at three granularities:

* **step**: steady-state per-block wall of (a) the fused single dispatch,
  (b) the PR-1/2 two-call chain (``block_coeffs`` + ``stream_step``), and
  (c) the fully unfused five-stage chain — fingerprint, binarize,
  signatures, insert, query as separate jitted calls with host
  round-trips between them (the "tuned in isolation" pipeline of the
  paper's motivation, which the fused step replaces).
* **e2e**: ``StreamingDetector.push`` chunks/sec, fused vs unfused at
  1 station and the vmapped station pool at 1 / 4 / 8 stations. All
  points are timed **interleaved** (every detector sees chunk k before
  any sees chunk k+1) and summarized by median per-push wall, so
  shared-machine noise phases hit every point equally instead of
  skewing whichever point they coincide with.
* **memory**: retained device bytes per chunk after warmup
  (``jax.live_arrays`` delta — 0 means every steady-state buffer is a
  donated in-place reuse) and peak host MB (tracemalloc), from a
  separate per-point pass.
* **offline_replay** (ISSUE 5): the unified batch driver —
  ``detect_events`` replaying an archive through the pooled streaming
  core, one fused dispatch per block for all stations — against a
  benchmark-local copy of the legacy host loop (per-station
  fingerprint → signatures → sort-based search → filter chains with
  blocking syncs between stages; the code this PR deleted from
  ``core/detect.py``), at 1/4/8 stations. Records batch blocks/sec and
  the legacy-vs-unified speedup (acceptance: unified ≥ legacy at 4
  stations on the quick run).
* **emission** (ISSUE 8): the device-side pair-compaction A/B at the
  paper-scale table count (t=100), compaction+verify on vs the dense
  t × N × cap emission, at 1 / 4 / 8 stations. Every point records the
  chunk-wall p50 *split* — fused device step vs host tail — plus the
  device→host pair bytes per block, so the O(T·N·C) → O(P) emission-
  pipe shrink is measured, not asserted. The stream is seeded with
  grid-aligned repeating events (``common.seed_repeating_events``) so
  every point emits real pairs; the v2 benchmark's streaming points all
  recorded ``pairs: 0`` and never exercised the path they timed.
  ``--emit`` refreshes only this section (``make bench-emit``).
* **sharded_pool** (ISSUE 10): the mesh-sharded station pool scaling
  grid. Each point forks a child interpreter under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=<d>`` (device count
  is fixed at backend init, so every device count needs its own
  process), streams identical repeat-seeded waveforms through the
  sharded pool and through the single-device ``vmap`` baseline, and
  records aggregate chunks/s, **exact** device-step percentiles, and
  per-station pair counts for the bit-parity check. ``--sharded``
  refreshes only this section (``make bench-sharded``).

Schema-stable output: ``BENCH_e2e.json`` with ``schema: "bench-e2e/v4"``
(v4: the ``sharded_pool`` device grid, and the per-point device-step/
host-tail percentiles are now **exact** wall-clock quantiles from raw
telemetry samples — the v3 values came from the log-bucketed registry
histograms, whose ``percentile()`` returns the bucket upper edge and
quantized every sub-2ms step onto 1.9531 ms; the histogram-derived
values remain under ``*_hist`` keys), a config hash, per-point
chunks/sec, and the headline ratios (fused speedup vs the unfused
chain; 4-/8-station pool wall vs 1-station; unified-batch speedup vs
the legacy loop; emission byte reduction + host-tail speedup; sharded
pool speedup at 8 stations × 8 devices). ``--quick`` shrinks the
stream for the tier-1-safe smoke invocation (``make bench-smoke`` /
the slow-marked pytest guard).
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import pathlib
import subprocess
import sys
import time
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (csv_line, frozen_smoke_stats,
                               seed_repeating_events)
from repro.configs.fast_seismic import (latency_config, smoke_config,
                                        stream_latency_smoke_config,
                                        stream_sharded_smoke_config)
from repro.core import align as A
from repro.core import fingerprint as F
from repro.core import lsh as L
from repro.core.detect import detect_events, replay_config
from repro.core.synth import SynthConfig, make_dataset
from repro.stream import engine as E
from repro.stream import fused as FU
from repro.stream import index as SI
from repro.stream.engine import StreamingDetector

SCHEMA = "bench-e2e/v4"

# (stations, fused) points; (1, False) is the unfused e2e reference
SPECS = [(1, True), (1, False), (4, True), (8, True)]


def pair_bytes_per_block(lcfg, scfg) -> int:
    """Device→host bytes one station's per-block pair emission costs.

    Dense: t × block × cap slots of (idx1, idx2, sim) int32/float32 +
    a valid byte = 13 B/slot. Compacted: ``max_pairs_per_block`` slots,
    +4 B/slot for the exact-Jaccard channel when verify is on."""
    if getattr(scfg, "max_pairs_per_block", 0) > 0:
        per = 13 + (4 if scfg.verify_jaccard else 0)
        return scfg.max_pairs_per_block * per
    return (lcfg.n_tables * scfg.block_fingerprints
            * scfg.index.bucket_cap) * 13


def _wall_split(det) -> dict:
    """p50 of the fused-dispatch and host-tail walls over the run
    (warmup pushes included — medians are robust to the handful of
    compile-adjacent outliers).

    The primary keys are **exact** quantiles over the raw wall samples
    (``telemetry.capture_raw_walls``, enabled by ``_detector``); the
    log-bucketed registry-histogram values — whose ``percentile()``
    returns the bucket upper edge and quantized every sub-2ms step onto
    the same 1.9531 ms — stay available under ``*_hist`` keys."""
    reg = det.telemetry.registry
    out = {
        "device_step_ms_p50_hist": round(
            reg.histogram_merged("fused_step_wall_seconds")
            .percentile(0.5) * 1e3, 4),
        "host_tail_ms_p50_hist": round(
            reg.histogram_merged("host_tail_wall_seconds")
            .percentile(0.5) * 1e3, 4),
    }
    raw = det.telemetry.raw_walls or {}
    for key, name in (("fused_step", "device_step_ms_p50"),
                      ("host_tail", "host_tail_ms_p50")):
        samples = raw.get(key)
        out[name] = (round(float(np.percentile(samples, 50)) * 1e3, 4)
                     if samples else out[f"{name}_hist"])
    return out


def config_hash(cfg, scfg) -> str:
    blob = json.dumps(
        {"cfg": dataclasses.asdict(cfg), "scfg": dataclasses.asdict(scfg)},
        sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _live_bytes() -> int:
    return sum(a.nbytes for a in jax.live_arrays())


def _timeit(fn, repeats: int, batches: int = 5) -> float:
    """Min-of-batches per-call seconds (robust to shared-machine noise:
    the minimum batch is the least-perturbed measurement)."""
    fn()
    fn()
    per = max(1, repeats // batches)
    best = float("inf")
    for _ in range(batches):
        t0 = time.perf_counter()
        for _ in range(per):
            fn()
        best = min(best, (time.perf_counter() - t0) / per)
    return best


def _detector(cfg, scfg, n_stations, fused, med_mad):
    scfg = dataclasses.replace(scfg, fused=fused, pooled=fused)
    det = StreamingDetector(cfg, scfg, n_stations=n_stations,
                            med_mad=med_mad)
    det.telemetry.capture_raw_walls()   # exact percentiles (_wall_split)
    return det


# ---------------------------------------------------------------------------
# step-level: one block through each pipeline shape
# ---------------------------------------------------------------------------


def step_points(cfg, scfg, repeats: int) -> dict:
    fcfg, lcfg = cfg.fingerprint, cfg.lsh
    block = scfg.block_fingerprints
    rng = np.random.default_rng(0)
    med = jnp.zeros(fcfg.n_coeff)
    mad = jnp.ones(fcfg.n_coeff)
    mp = L.hash_mappings(fcfg.fp_dim, lcfg)
    blockw = jnp.asarray(
        rng.standard_normal(fcfg.block_samples(block)).astype(np.float32))
    adv = blockw[-block * fcfg.lag_samples:]
    ids = jnp.arange(block, dtype=jnp.int32)
    vmask = jnp.ones(block, bool)

    # (a) fused single dispatch (donated state, device halo)
    hold = {"s": FU.init_state(SI.init_index(lcfg, scfg.index),
                               fcfg.halo_samples, med, mad)}

    def fused_step():
        hold["s"], p, _ = FU.step_advance(hold["s"], adv, mp, jnp.int32(0),
                                          fcfg, lcfg, 0)
        jax.block_until_ready(p.valid)

    t_fused = _timeit(fused_step, repeats)

    # (b) the PR-1/2 two-call chain
    hold2 = {"s": SI.init_index(lcfg, scfg.index)}

    def two_call():
        coeffs = E.block_coeffs(blockw, fcfg)
        hold2["s"], p, _ = E.stream_step(hold2["s"], coeffs, med, mad, mp,
                                         jnp.int32(0), vmask, fcfg, lcfg, 0)
        jax.block_until_ready(p.valid)

    t_two = _timeit(two_call, repeats)

    # (c) fully unfused: every stage its own jitted call, host round-trips
    # between them (fingerprinting / hashing / search tuned in isolation)
    binarize = jax.jit(
        lambda c, m1, m2: F.binarize_coeffs(c, fcfg, (m1, m2))[0])
    signatures = jax.jit(lambda b: L.signatures(b, mp, lcfg))
    hold5 = {"s": SI.init_index(lcfg, scfg.index)}

    def stage_chain():
        coeffs = np.asarray(E.block_coeffs(blockw, fcfg))
        bits = np.asarray(binarize(jnp.asarray(coeffs), med, mad))
        sigs = jnp.asarray(np.asarray(signatures(jnp.asarray(bits))))
        hold5["s"] = SI.insert(hold5["s"], sigs, ids, lcfg)
        p = SI.query(hold5["s"], sigs, ids, lcfg)
        jax.block_until_ready(p.valid)

    t_chain = _timeit(stage_chain, repeats)

    csv_line("e2e.step_fused", t_fused * 1e6, f"block={block} dispatches=1")
    csv_line("e2e.step_two_call", t_two * 1e6,
             f"speedup_fused={t_two / t_fused:.2f}x")
    csv_line("e2e.step_unfused_chain", t_chain * 1e6,
             f"speedup_fused={t_chain / t_fused:.2f}x dispatches=5")
    return {
        "block_fingerprints": block,
        "fused_ms": round(t_fused * 1e3, 4),
        "two_call_ms": round(t_two * 1e3, 4),
        "unfused_chain_ms": round(t_chain * 1e3, 4),
    }


# ---------------------------------------------------------------------------
# offline replay: the unified batch driver vs the legacy host loop
# ---------------------------------------------------------------------------


def _legacy_detect_loop(waveforms, cfg):
    """Benchmark-local copy of the pre-unification ``detect_events`` host
    loop (per-station stage chains, four blocking syncs per station) —
    the baseline the unified replay driver is measured against."""
    fcfg, lcfg, acfg = cfg.fingerprint, cfg.lsh, cfg.align
    station_events = []
    for st in range(waveforms.shape[0]):
        x = jnp.asarray(waveforms[st])
        bits, _ = F.fingerprints_from_waveform(
            x, fcfg, key=jax.random.PRNGKey(fcfg.stft_len + st))
        jax.block_until_ready(bits)
        mp = L.hash_mappings(fcfg.fp_dim, lcfg)
        sigs = L.signatures(bits, mp, lcfg)
        jax.block_until_ready(sigs)
        pairs = L.candidate_pairs(sigs, lcfg)
        if lcfg.occurrence_frac > 0:
            pairs, _ = L.occurrence_filter(pairs, bits.shape[0],
                                           lcfg.occurrence_frac)
        jax.block_until_ready(pairs.valid)
        merged = A.merge_channels(
            [(pairs.dt, pairs.idx1, pairs.sim, pairs.valid)],
            acfg.channel_threshold)
        events = A.cluster_station(merged, acfg)
        jax.block_until_ready(events.valid)
        station_events.append(events)
    det = A.associate_network(station_events, acfg, waveforms.shape[0])
    jax.block_until_ready(det["valid"])
    return det


def offline_replay_points(duration_s: float, repeats: int = 3) -> dict:
    """Batch archive reprocessing: unified core vs legacy loop, 1/4/8
    stations. Both drivers run the identical detection semantics (the
    unified pair set is golden-pinned bit-exact against the legacy one),
    so the comparison is pure orchestration cost: one pooled fused
    dispatch per block vs per-station per-stage dispatches + syncs."""
    cfg = smoke_config()
    scfg = replay_config(cfg.lsh, block_fingerprints=64, n_buckets=2048)
    ds = make_dataset(SynthConfig(duration_s=duration_s, n_stations=8,
                                  n_sources=2, events_per_source=4,
                                  event_snr=3.0, seed=7))
    n_fp = cfg.fingerprint.n_fingerprints(ds.waveforms.shape[1])
    n_blocks = -(-n_fp // scfg.block_fingerprints)
    points = []
    for s in (1, 4, 8):
        wf = ds.waveforms[:s]

        def unified():
            return detect_events(wf, cfg, scfg=scfg)

        def legacy():
            return _legacy_detect_loop(wf, cfg)

        for fn in (unified, legacy):    # compile both before timing
            fn()
        t_uni = float(np.median([_wall(unified) for _ in range(repeats)]))
        t_leg = float(np.median([_wall(legacy) for _ in range(repeats)]))
        point = {
            "stations": s,
            "fingerprints": n_fp,
            "blocks": n_blocks,
            "unified_wall_ms": round(t_uni * 1e3, 2),
            "unified_blocks_per_s": round(n_blocks / max(t_uni, 1e-9), 2),
            "legacy_wall_ms": round(t_leg * 1e3, 2),
            "speedup_vs_legacy": round(t_leg / max(t_uni, 1e-9), 3),
        }
        csv_line(f"e2e.offline_replay_s{s}", t_uni * 1e6,
                 f"legacy={t_leg * 1e6:.0f}us "
                 f"speedup={point['speedup_vs_legacy']}x")
        points.append(point)
    return {
        "duration_s": duration_s,
        "block_fingerprints": scfg.block_fingerprints,
        "points": points,
        "speedup_vs_legacy_4st": next(
            p["speedup_vs_legacy"] for p in points if p["stations"] == 4),
    }


def _wall(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# end-to-end detector throughput + allocation behaviour
# ---------------------------------------------------------------------------


def interleaved_walls(cfg, scfg, wf, med_mad, n_chunks: int,
                      warmup: int) -> tuple[dict, dict, dict]:
    """Per-spec median ``push`` wall, measured round-robin per chunk.

    Also returns each spec's device-step/host-tail wall split (from the
    detector's own telemetry histograms) and the flagship 4-station
    pooled detector's ``metrics_snapshot()`` (ISSUE 6) — the structured
    telemetry view of the timed stream, embedded in ``BENCH_e2e.json``
    so a perf regression comes with its drop/quality/wall-histogram
    context attached."""
    dets = {k: _detector(cfg, scfg, k[0], k[1], med_mad) for k in SPECS}
    split = {k: np.array_split(wf[:k[0]], n_chunks, axis=1)
             for k in SPECS}
    for k, det in dets.items():
        for c in split[k][:warmup]:
            det.push(c)
    walls = {k: [] for k in SPECS}
    for i in range(warmup, n_chunks):
        for k, det in dets.items():
            t0 = time.perf_counter()
            det.push(split[k][i])
            walls[k].append(time.perf_counter() - t0)
    metrics = dets[(4, True)].metrics_snapshot()
    splits = {k: _wall_split(det) for k, det in dets.items()}
    return {k: float(np.median(w)) for k, w in walls.items()}, splits, \
        metrics


def memory_point(cfg, scfg, wf, med_mad, n_stations: int, fused: bool,
                 n_chunks: int, warmup: int) -> dict:
    """Retained-bytes + host-peak pass for one point (untimed).

    ``gc.collect()`` before each live-array snapshot: buffers abandoned
    by *earlier* benchmark phases (e.g. the offline-replay drivers) must
    not be collected mid-measurement and show up as a phantom negative
    delta on this point."""
    import gc
    det = _detector(cfg, scfg, n_stations, fused, med_mad)
    chunks = np.array_split(wf[:n_stations], n_chunks, axis=1)
    tracemalloc.start()
    for c in chunks[:warmup]:
        det.push(c)
    gc.collect()
    live0 = _live_bytes()
    for c in chunks[warmup:]:
        det.push(c)
    gc.collect()
    live_delta = _live_bytes() - live0
    _, host_peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    timed = n_chunks - warmup
    return {
        "live_bytes_delta_per_chunk": int(live_delta / max(timed, 1)),
        "peak_host_mb": round(host_peak / 2**20, 3),
        "pairs": int(sum(st.stats.pairs for st in det.stations)),
    }


# ---------------------------------------------------------------------------
# emission A/B: device-side compaction + verify vs the dense pipe (ISSUE 8)
# ---------------------------------------------------------------------------


def emission_points(duration_s: float) -> dict:
    """Compaction on/off A/B at the paper-scale table count (t=100).

    Same latency-regime fingerprints, LSH widened to t=100 (the §6.3
    setting whose dense emission the compaction targets): one station's
    dense pipe is 100 × 4 × 8 = 3 200 slots per block; compacted it is
    ``max_pairs=128``. Both variants stream the same repeat-seeded
    waveforms through fused pooled detectors at 1 / 4 / 8 stations,
    interleaved per chunk (each sextet of detectors sees chunk k before
    any sees k+1); every point records the chunk p50 plus its device-
    step / host-tail split and the computed transfer bytes per block.
    """
    cfg = latency_config()
    cfg = dataclasses.replace(
        cfg, lsh=dataclasses.replace(cfg.lsh, n_tables=100))
    base = stream_latency_smoke_config()
    dense = dataclasses.replace(
        base, index=dataclasses.replace(base.index, bucket_cap=8))
    compact = dataclasses.replace(
        dense, max_pairs_per_block=128, verify_jaccard=True,
        index=dataclasses.replace(dense.index, bucket_cap=8,
                                  pk_slots=8192))
    ds = make_dataset(SynthConfig(duration_s=duration_s, n_stations=8,
                                  n_sources=2, events_per_source=4,
                                  event_snr=3.0, seed=7))
    wf = seed_repeating_events(np.asarray(ds.waveforms),
                               cfg.fingerprint.lag_samples)
    med_mad = frozen_smoke_stats(cfg, wf[0])
    n_chunks = int(wf.shape[1] // (dense.block_fingerprints
                                   * cfg.fingerprint.lag_samples))
    warmup = max(4, n_chunks // 10)

    specs = [(s, v) for s in (1, 4, 8) for v in ("dense", "compact")]
    scfgs = {"dense": dense, "compact": compact}
    dets = {k: _detector(cfg, scfgs[k[1]], k[0], True, med_mad)
            for k in specs}
    split = {k: np.array_split(wf[:k[0]], n_chunks, axis=1) for k in specs}
    for k, det in dets.items():
        for c in split[k][:warmup]:
            det.push(c)
    walls = {k: [] for k in specs}
    for i in range(warmup, n_chunks):
        for k, det in dets.items():
            t0 = time.perf_counter()
            det.push(split[k][i])
            walls[k].append(time.perf_counter() - t0)

    points = []
    for k in specs:
        s, variant = k
        det, scfg_v = dets[k], scfgs[variant]
        point = {"stations": s, "variant": variant,
                 "chunk_ms_p50": round(float(np.median(walls[k])) * 1e3, 4),
                 "pairs": int(sum(st.stats.pairs for st in det.stations)),
                 "overflow_pairs": int(det.telemetry.drop_breakdown()
                                       .get("overflow_pairs", 0)),
                 "pair_bytes_per_block":
                     pair_bytes_per_block(cfg.lsh, scfg_v)}
        point.update(_wall_split(det))
        csv_line(f"e2e.emission_s{s}_{variant}",
                 float(np.median(walls[k])) * 1e6,
                 f"pairs={point['pairs']} "
                 f"bytes/block={point['pair_bytes_per_block']} "
                 f"host_tail_p50={point['host_tail_ms_p50']}ms")
        points.append(point)

    def pt(s, v):
        return next(p for p in points if p["stations"] == s
                    and p["variant"] == v)

    return {
        "duration_s": duration_s,
        "n_tables": cfg.lsh.n_tables,
        "block_fingerprints": dense.block_fingerprints,
        "max_pairs_per_block": compact.max_pairs_per_block,
        "points": points,
        "pair_byte_reduction_t100": round(
            pt(1, "dense")["pair_bytes_per_block"]
            / pt(1, "compact")["pair_bytes_per_block"], 2),
        "host_tail_speedup_8st": round(
            pt(8, "dense")["host_tail_ms_p50"]
            / max(pt(8, "compact")["host_tail_ms_p50"], 1e-6), 3),
    }


# ---------------------------------------------------------------------------
# sharded station pool: device-count × stations scaling grid (ISSUE 10)
# ---------------------------------------------------------------------------


def sharded_child(spec: dict) -> dict:
    """One grid point, run inside a forced-device-count interpreter.

    Streams identical repeat-seeded noise through (a) the mesh-sharded
    pool and (b) the single-device ``vmap`` pool (``sharded=False``),
    interleaved per chunk so machine-noise phases hit both equally.
    Device-step percentiles are exact (raw telemetry samples, warmup
    excluded); the per-station pair counts feed the parent's bit-parity
    check — the two variants must agree exactly on clean data."""
    n_stations = int(spec["stations"])
    n_chunks = int(spec.get("chunks", 32))
    # warmup must cover stats freeze + the full-frame block compile +
    # the steady advance compile, for BOTH variants, or the first timed
    # chunk of one variant eats a compile the other got for free
    warmup = max(4, n_chunks // 8)
    cfg, base = latency_config(), stream_sharded_smoke_config()
    fcfg = cfg.fingerprint
    chunk = base.block_fingerprints * fcfg.lag_samples
    rng = np.random.default_rng(7)
    wf = rng.standard_normal((n_stations, n_chunks * chunk)) \
        .astype(np.float32)
    wf = seed_repeating_events(wf, fcfg.lag_samples)
    med_mad = frozen_smoke_stats(cfg, wf[0])
    chunks = np.array_split(wf, n_chunks, axis=1)

    variants = {
        "sharded": _detector(cfg, base, n_stations, True, med_mad),
        "baseline": _detector(
            cfg, dataclasses.replace(base, sharded=False), n_stations,
            True, med_mad),
    }
    for det in variants.values():
        for c in chunks[:warmup]:
            det.push(c)
        det.telemetry.raw_walls["fused_step"].clear()
    walls = {k: [] for k in variants}
    for c in chunks[warmup:]:
        for k, det in variants.items():
            t0 = time.perf_counter()
            det.push(c)
            walls[k].append(time.perf_counter() - t0)

    out = {"devices": jax.device_count(), "stations": n_stations,
           "chunks": n_chunks - warmup}
    for k, det in variants.items():
        steps = det.telemetry.raw_walls["fused_step"]
        out[k] = {
            "mesh_devices": int(det.mesh.devices.size) if det.mesh else 1,
            "pool_pad": det.pool_pad,
            "chunks_per_s": round(
                (n_chunks - warmup) / max(sum(walls[k]), 1e-9), 3),
            "device_step_ms_p50": round(
                float(np.percentile(steps, 50)) * 1e3, 4),
            "device_step_ms_p95": round(
                float(np.percentile(steps, 95)) * 1e3, 4),
            "pairs": [int(st.stats.pairs) for st in det.stations],
        }
    out["pair_parity"] = out["sharded"]["pairs"] == out["baseline"]["pairs"]
    out["speedup_vs_vmap"] = round(
        out["sharded"]["chunks_per_s"]
        / max(out["baseline"]["chunks_per_s"], 1e-9), 3)
    return out


def sharded_pool_points(quick: bool) -> dict:
    """Fan the (device count × stations) grid out over child
    interpreters: ``--xla_force_host_platform_device_count`` binds at
    backend init, so each device count needs a fresh process. The
    flagship point (8 stations × 8 devices, one station per device) is
    in both grids — the acceptance ratio reads from it.

    CPU only: the children time forced host devices, which next to an
    accelerator would quietly measure the host instead of the chip."""
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"the sharded_pool grid runs forced CPU host devices in child "
            f"processes and cannot measure a {backend} backend")
    root = pathlib.Path(__file__).resolve().parent.parent
    grid = [(2, 4), (8, 8)] if quick else \
        [(1, 8), (2, 8), (4, 8), (8, 8), (8, 16)]
    n_chunks = 24 if quick else 48
    points = []
    for devices, stations in grid:
        spec = {"devices": devices, "stations": stations,
                "chunks": n_chunks}
        env = dict(
            os.environ,
            PYTHONPATH=f"{root / 'src'}:{root}",
            JAX_PLATFORMS="cpu",
            XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
        r = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_e2e",
             "--sharded-child", json.dumps(spec)],
            capture_output=True, text=True, env=env, cwd=root,
            timeout=1800)
        if r.returncode != 0:
            raise RuntimeError(
                f"sharded child {spec} failed:\n{r.stdout}\n{r.stderr}")
        point = json.loads(r.stdout.strip().splitlines()[-1])
        assert point["pair_parity"], \
            f"sharded/vmap pair mismatch at {spec}: " \
            f"{point['sharded']['pairs']} vs {point['baseline']['pairs']}"
        csv_line(f"e2e.sharded_d{devices}_s{stations}",
                 1e6 / max(point["sharded"]["chunks_per_s"], 1e-9),
                 f"speedup_vs_vmap={point['speedup_vs_vmap']}x "
                 f"step_p50={point['sharded']['device_step_ms_p50']}ms")
        points.append(point)
    flagship = next((p for p in points
                     if p["devices"] == 8 and p["stations"] == 8), None)
    return {
        "block_fingerprints":
            stream_sharded_smoke_config().block_fingerprints,
        # forced host devices time-slice the physical cores: with fewer
        # cores than devices the parallel speedup is capped at
        # cores/1 — on a 1-core host the flagship ratio reads the pure
        # sharding overhead (≤ 1x), not the scaling curve
        "host_cores": len(os.sched_getaffinity(0)),
        "points": points,
        "speedup_8st_8dev":
            flagship["speedup_vs_vmap"] if flagship else None,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tier-1-safe smoke run (short stream)")
    ap.add_argument("--duration-s", type=float, default=0.0,
                    help="override stream length (0 = 240 normal/60 quick)")
    ap.add_argument("--step-repeats", type=int, default=0)
    ap.add_argument("--emit", action="store_true",
                    help="refresh only the emission A/B section of an "
                         "existing BENCH_e2e.json (make bench-emit)")
    ap.add_argument("--sharded", action="store_true",
                    help="refresh only the sharded_pool grid of an "
                         "existing BENCH_e2e.json (make bench-sharded)")
    ap.add_argument("--sharded-child", metavar="JSON",
                    help="internal: run one sharded grid point in this "
                         "(forced-device-count) interpreter and print "
                         "its JSON result")
    args = ap.parse_args(argv)

    if args.sharded_child:
        print(json.dumps(sharded_child(json.loads(args.sharded_child))))
        return None
    duration = args.duration_s or (60.0 if args.quick else 240.0)
    repeats = args.step_repeats or (50 if args.quick else 250)

    out_dir = os.environ.get("BENCH_OUT_DIR", ".")
    path = os.path.join(out_dir, "BENCH_e2e.json")

    if args.sharded:
        sharded = sharded_pool_points(args.quick)
        out = {"schema": SCHEMA}
        if os.path.exists(path):
            with open(path) as f:
                out = json.load(f)
            out["schema"] = SCHEMA
        out["sharded_pool"] = sharded
        out.setdefault("ratios", {})
        out["ratios"]["sharded_pool_speedup_8st_8dev"] = \
            sharded["speedup_8st_8dev"]
        with open(path, "w") as f:
            json.dump(out, f, indent=2)
        print(f"# wrote {path} (sharded_pool section)")
        print(f"# sharded pool @8st x 8dev: "
              f"{sharded['speedup_8st_8dev']}x vs single-device vmap")
        return out

    if args.emit:
        emission = emission_points(duration)
        out = {"schema": SCHEMA}
        if os.path.exists(path):
            with open(path) as f:
                out = json.load(f)
            out["schema"] = SCHEMA
        out["emission"] = emission
        out.setdefault("ratios", {})
        out["ratios"]["emission_pair_byte_reduction_t100"] = \
            emission["pair_byte_reduction_t100"]
        out["ratios"]["emission_host_tail_speedup_8st"] = \
            emission["host_tail_speedup_8st"]
        with open(path, "w") as f:
            json.dump(out, f, indent=2)
        print(f"# wrote {path} (emission section)")
        print(f"# emission bytes/block t=100: "
              f"{emission['pair_byte_reduction_t100']}x smaller; "
              f"host tail @8st: {emission['host_tail_speedup_8st']}x")
        return out

    cfg, scfg = latency_config(), stream_latency_smoke_config()
    ds = make_dataset(SynthConfig(duration_s=duration, n_stations=8,
                                  n_sources=2, events_per_source=4,
                                  event_snr=3.0, seed=7))
    # grid-aligned repeating events: streaming points emit real pairs,
    # so the timed path includes actual emission/host-tail work (the v2
    # points all recorded pairs: 0)
    wf = seed_repeating_events(np.asarray(ds.waveforms),
                               cfg.fingerprint.lag_samples)
    med_mad = frozen_smoke_stats(cfg, wf[0])

    # one chunk per block advance: the per-arrival serving cadence
    n_chunks = int(wf.shape[1]
                   // (scfg.block_fingerprints
                       * cfg.fingerprint.lag_samples))
    warmup = max(4, n_chunks // 10)

    step = step_points(cfg, scfg, repeats)
    replay = offline_replay_points(duration)
    emission = emission_points(duration)
    sharded = sharded_pool_points(args.quick)
    walls, splits, metrics = interleaved_walls(cfg, scfg, wf, med_mad,
                                               n_chunks, warmup)
    points = []
    for k in SPECS:
        n_stations, fused = k
        point = {"stations": n_stations, "fused": fused,
                 "chunks": n_chunks - warmup,
                 "chunk_ms_p50": round(walls[k] * 1e3, 4),
                 "chunks_per_s": round(1.0 / max(walls[k], 1e-9), 2),
                 "pair_bytes_per_block":
                     pair_bytes_per_block(cfg.lsh, scfg)}
        point.update(splits[k])
        point.update(memory_point(cfg, scfg, wf, med_mad, n_stations,
                                  fused, n_chunks, warmup))
        csv_line(f"e2e.push_s{n_stations}_{'fused' if fused else 'unfused'}",
                 walls[k] * 1e6,
                 f"chunks_per_s={point['chunks_per_s']} "
                 f"live_delta={point['live_bytes_delta_per_chunk']}B/chunk")
        points.append(point)

    ratios = {
        "fused_speedup_vs_unfused_chain": round(
            step["unfused_chain_ms"] / step["fused_ms"], 3),
        "fused_speedup_vs_two_call": round(
            step["two_call_ms"] / step["fused_ms"], 3),
        "e2e_fused_speedup_vs_unfused_1st": round(
            walls[(1, False)] / walls[(1, True)], 3),
        "pool_wall_x_4st_vs_1st": round(
            walls[(4, True)] / walls[(1, True)], 3),
        "pool_wall_x_8st_vs_1st": round(
            walls[(8, True)] / walls[(1, True)], 3),
        "offline_replay_speedup_vs_legacy_4st":
            replay["speedup_vs_legacy_4st"],
        "emission_pair_byte_reduction_t100":
            emission["pair_byte_reduction_t100"],
        "emission_host_tail_speedup_8st":
            emission["host_tail_speedup_8st"],
        "sharded_pool_speedup_8st_8dev": sharded["speedup_8st_8dev"],
    }
    out = {
        "schema": SCHEMA,
        "config_hash": config_hash(cfg, scfg),
        "backend": jax.default_backend(),
        "quick": bool(args.quick),
        "duration_s": duration,
        "step": step,
        "points": points,
        "offline_replay": replay,
        "emission": emission,
        "sharded_pool": sharded,
        "ratios": ratios,
        "metrics": metrics,
    }
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"# wrote {path}")
    print(f"# fused vs unfused chain: "
          f"{ratios['fused_speedup_vs_unfused_chain']}x; "
          f"8-station pool wall: {ratios['pool_wall_x_8st_vs_1st']}x "
          f"1-station; offline replay vs legacy loop @4st: "
          f"{replay['speedup_vs_legacy_4st']}x; emission pipe @t=100: "
          f"{emission['pair_byte_reduction_t100']}x fewer bytes/block; "
          f"sharded pool @8st x 8dev: {sharded['speedup_8st_8dev']}x")
    return out


if __name__ == "__main__":
    main()
