"""Full FAST workload: multi-station detection with every paper
optimization toggled, reporting a factor-analysis-style breakdown
(paper §8.1) and final network detections vs injected ground truth.

``detect_events`` is the unified batch driver (one core, two drivers):
each configuration replays the archive through the streaming station-pool
step — one fused dispatch per block for all stations — so the streaming
data-quality guards are available to batch runs too. ``--block-fp`` sizes
the replay block; ``--occ-limit`` turns on the in-dispatch §6.5
occurrence limiter for the optimized configuration (useful when
reprocessing archives with known glitch trains).

Run:  PYTHONPATH=src python examples/detect_earthquakes.py [--duration 900]
"""
import argparse
import dataclasses
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (AlignConfig, DetectConfig, FingerprintConfig,
                        LSHConfig, SynthConfig, make_dataset)
from repro.core.detect import detect_events, recall_against_truth, \
    replay_config


def run(cfg_name: str, cfg: DetectConfig, waveforms, dataset, scfg=None):
    t0 = time.perf_counter()
    det, events, times, stats = detect_events(waveforms, cfg, scfg=scfg)
    wall = time.perf_counter() - t0
    rec = recall_against_truth(det, events, dataset, cfg.fingerprint)
    print(f"{cfg_name:28s} wall={wall:6.1f}s "
          f"detections={stats['detections']:3d} "
          f"recall={rec['recall']:.2f} "
          f"(stats={times.fingerprint_s:.1f} hash={times.hashgen_s:.1f} "
          f"fused={times.fused_step_s:.1f} align={times.align_s:.1f})")
    return wall, rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=float, default=600.0)
    ap.add_argument("--block-fp", type=int, default=256,
                    help="replay block size (fingerprints per dispatch)")
    ap.add_argument("--occ-limit", type=int, default=0,
                    help="in-dispatch occurrence limiter for the optimized "
                         "run (0 = off; host §6.5 filter always applies)")
    args = ap.parse_args()
    enable_compile_cache()

    dataset = make_dataset(SynthConfig(
        duration_s=args.duration, n_stations=3, n_sources=3,
        events_per_source=4, event_snr=3.0,
        repeating_noise_stations=(0,), hum_stations=(2,), seed=11))
    wf = dataset.waveforms
    print(f"dataset: {wf.shape[0]} stations × {wf.shape[1]} samples, "
          f"{len(dataset.event_times)} injected events\n")

    fp = FingerprintConfig(img_time=32, img_hop=4, top_k=200,
                           mad_sample_rate=1.0)
    base_align = AlignConfig(channel_threshold=3, min_cluster_sim=4,
                             min_cluster_size=1, min_stations=2,
                             onset_tol=int(10 * fp.fs / fp.lag_samples))

    # paper-faithful baseline: MinHash, no occurrence filter, full MAD
    baseline = DetectConfig(
        fingerprint=fp,
        lsh=LSHConfig(n_tables=100, n_funcs=4, n_matches=5,
                      use_minmax=False, min_dt=fp.overlap_fingerprints,
                      occurrence_frac=0.0),
        align=base_align)
    t_base, _ = run("baseline(minhash,k4m5)", baseline, wf, dataset)

    # + occurrence filter (§6.5)
    occ = dataclasses.replace(
        baseline, lsh=dataclasses.replace(baseline.lsh,
                                          occurrence_frac=0.05))
    run("+occurrence_filter", occ, wf, dataset)

    # + k↑ m↓ with matched S-curve (§6.3)
    kfun = dataclasses.replace(
        occ, lsh=dataclasses.replace(occ.lsh, n_funcs=6, n_matches=1))
    run("+increase_hash_funcs", kfun, wf, dataset)

    # + Min-Max hash (§6.2)
    mm = dataclasses.replace(
        kfun, lsh=dataclasses.replace(kfun.lsh, use_minmax=True))
    run("+minmax_hash", mm, wf, dataset)

    # + sampled MAD (§5.2) — the fully-optimized pipeline, with the
    # replay knobs threaded through (block size + in-dispatch limiter)
    opt = dataclasses.replace(
        mm, fingerprint=dataclasses.replace(fp, mad_sample_rate=0.1))
    scfg = replay_config(opt.lsh, block_fingerprints=args.block_fp)
    if args.occ_limit:
        scfg = dataclasses.replace(
            scfg, occ_limit=args.occ_limit,
            index=dataclasses.replace(
                scfg.index,
                occ_slots=opt.fingerprint.n_fingerprints(wf.shape[1])))
    t_opt, rec = run("+mad_sampling(=optimized)", opt, wf, dataset,
                     scfg=scfg)

    print(f"\ncumulative speedup: {t_base / t_opt:.1f}×  "
          f"final recall: {rec['recall']:.2f}")


if __name__ == "__main__":
    main()
