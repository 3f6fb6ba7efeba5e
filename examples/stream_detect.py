"""Streaming FAST: continuous multi-station detection over chunked input.

The offline pipeline (examples/detect_earthquakes.py) sees the whole trace
at once; here the same synthetic network arrives as ~1-minute chunks and
the ``StreamingDetector`` maintains a device-resident incremental LSH
index per station — each chunk costs O(chunk), no re-sort of history.
Finishes by comparing streamed detections against the injected ground
truth and against an offline re-run of the identical configuration.

With ``--bounded`` the detector runs in the sliding-window regime: index
entries expire beyond the detection window, candidate pairs retire through
the rolling occurrence filter (host state bounded by the window, not the
stream), and multi-station detections print as near-real-time alerts the
moment their windows close instead of only at finalize.

With ``--locate`` (implies ``--bounded``) the synthetic network gets real
station geometry and physical moveouts, and the ISSUE-9 location tier runs
on every association: alerts carry a migration-stacked origin and a
relative magnitude, moveout-inconsistent coincidences are rejected, and
upgraded alerts (a station joining late) re-emit flagged.

Run:  PYTHONPATH=src python examples/stream_detect.py [--duration 600]
      PYTHONPATH=src python examples/stream_detect.py --bounded
      PYTHONPATH=src python examples/stream_detect.py --locate
"""
import argparse
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.fast_seismic import (located_smoke_config, smoke_config,
                                        stream_bounded_smoke_config,
                                        stream_smoke_config)
from repro.core import SynthConfig, make_dataset
from repro.core.detect import detect_events, recall_against_truth
from repro.core.locate import LOC_NONE, MAG_NONE
from repro.stream import StreamingDetector


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=float, default=600.0)
    ap.add_argument("--chunk-s", type=float, default=60.0)
    ap.add_argument("--stations", type=int, default=3)
    ap.add_argument("--bounded", action="store_true",
                    help="sliding window + rolling filter + live alerts")
    ap.add_argument("--locate", action="store_true",
                    help="station geometry + location/magnitude tier "
                         "(implies --bounded)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = located_smoke_config() if args.locate else smoke_config()
    scfg = (stream_bounded_smoke_config() if args.bounded or args.locate
            else stream_smoke_config())
    dataset = make_dataset(SynthConfig(
        duration_s=args.duration, n_stations=args.stations, n_sources=3,
        events_per_source=4, event_snr=3.0,
        repeating_noise_stations=(0,), seed=11,
        physical_geometry=args.locate))
    wf = dataset.waveforms
    chunk = int(args.chunk_s * cfg.fingerprint.fs)

    det = StreamingDetector(cfg, scfg, n_stations=args.stations,
                            station_xy=dataset.station_xy)
    t0 = time.perf_counter()
    for start in range(0, wf.shape[1], chunk):
        n_alerts = len(det.alerts)
        det.push(wf[:, start: start + chunk])
        for rows in det.alerts[n_alerts:]:
            for dt, onset, n_st, score, upg, x_mkm, y_mkm, mag_m in rows:
                lag_s = cfg.fingerprint.lag_samples / cfg.fingerprint.fs
                where = ("" if x_mkm == LOC_NONE else
                         f" at ({x_mkm / 1e3:.1f}, {y_mkm / 1e3:.1f}) km")
                size = ("" if mag_m == MAG_NONE
                        else f" dmag={mag_m / 1e3:+.2f}")
                tag = " UPGRADE" if upg else ""
                print(f"  ALERT t≈{onset * lag_s:6.0f}s dt={dt * lag_s:.0f}s "
                      f"stations={n_st} score={score}{where}{size}{tag} "
                      f"(stream at {(start + chunk) / cfg.fingerprint.fs:.0f}s)")
    detections, events, stats = det.finalize()
    stream_wall = time.perf_counter() - t0
    rec = recall_against_truth(detections, events, dataset, cfg.fingerprint)
    ing = stats["ingest"][0]
    print(f"streaming   wall={stream_wall:6.1f}s "
          f"detections={stats.get('detections', 0):3d} "
          f"recall={rec['recall']:.2f} "
          f"(chunk p50={ing['chunk_ms_p50']:.0f}ms "
          f"p95={ing['chunk_ms_p95']:.0f}ms "
          f"{ing['samples_per_s']:.0f} samples/s/station)")
    # the ISSUE-6 telemetry view: real-time factor, in-dispatch drop
    # breakdown, wall histograms — the same snapshot serve_detect and the
    # BENCH artifacts embed
    m = det.metrics_snapshot()
    fused_p95_ms = 1e3 * m["histograms"]["fused_step_wall_seconds"]["p95"]
    print(f"telemetry   rtf={m['rtf']:.0f}x realtime "
          f"pairs={m['drops']['pairs_emitted']} "
          f"masked={m['drops']['masked_fingerprints']} "
          f"limited={m['drops']['limited_pairs']} "
          f"fused p95={fused_p95_ms:.1f}ms steps={m['watchdog']['steps']} "
          f"stragglers={m['watchdog']['stragglers']}")

    if args.locate and detections is not None:
        v = np.asarray(detections["valid"])
        errs = [np.min(np.linalg.norm(
                    dataset.source_xy
                    - np.array([detections["x_km"][g],
                                detections["y_km"][g]]), axis=1))
                for g in np.nonzero(v)[0]]
        lv = det.telemetry.locate_view()
        med = f"{np.median(errs):.1f}" if errs else "n/a"
        print(f"located     {int(v.sum()):3d} detections "
              f"median_origin_err={med} km "
              f"moveout_rejected={lv['moveout_rejected']} "
              f"stack p50={lv['stack_wall']['p50_ms']:.1f}ms")

    t0 = time.perf_counter()
    off_det, off_events, _, off_stats = detect_events(
        wf, cfg, station_xy=dataset.station_xy)
    off_wall = time.perf_counter() - t0
    off_rec = recall_against_truth(off_det, off_events, dataset,
                                   cfg.fingerprint)
    print(f"offline     wall={off_wall:6.1f}s "
          f"detections={off_stats['detections']:3d} "
          f"recall={off_rec['recall']:.2f}")


if __name__ == "__main__":
    main()
