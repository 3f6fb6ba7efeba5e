"""Quickstart: detect reoccurring earthquakes in 10 minutes of synthetic
seismic data — the paper's full pipeline through the public API.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (AlignConfig, DetectConfig, FingerprintConfig,
                        LSHConfig, SynthConfig, make_dataset)
from repro.core.detect import detect_events, recall_against_truth


def main():
    enable_compile_cache()
    # 1. Synthetic network: 3 stations, 3 reoccurring sources, repeating
    #    background noise at station 0 (the Figure-7 pathology).
    dataset = make_dataset(SynthConfig(
        duration_s=600.0, n_stations=3, n_sources=3, events_per_source=4,
        event_snr=3.0, repeating_noise_stations=(0,), seed=3))
    print(f"waveforms: {dataset.waveforms.shape} "
          f"({len(dataset.event_times)} injected events)")

    # 2. Pipeline config (paper Figure 2: fingerprint → LSH → align).
    fp = FingerprintConfig(img_time=32, img_hop=4, top_k=200,
                           mad_sample_rate=0.5)
    cfg = DetectConfig(
        fingerprint=fp,
        lsh=LSHConfig(n_tables=100, n_funcs=4, n_matches=2,
                      min_dt=fp.overlap_fingerprints,
                      occurrence_frac=0.05),
        align=AlignConfig(channel_threshold=3, min_cluster_sim=4,
                          min_cluster_size=1, min_stations=2,
                          onset_tol=int(10 * fp.fs / fp.lag_samples)))

    # 3. Detect.
    detections, station_events, times, stats = detect_events(
        dataset.waveforms, cfg)
    # batch = replay over the streaming core: the fused per-block dispatch
    # (fingerprint→hash→search in one program) is its own span-derived
    # stage, fused_step_s (search_s remains as a legacy alias)
    print(f"stage seconds: stats={times.fingerprint_s:.1f} "
          f"hashgen={times.hashgen_s:.1f} "
          f"fused_replay={times.fused_step_s:.1f} "
          f"align={times.align_s:.1f}")
    print(f"network detections: {stats['detections']}")

    # 4. Score against injected ground truth.
    rec = recall_against_truth(detections, station_events, dataset,
                               cfg.fingerprint)
    print(f"recall on reoccurring events: {rec['hits']}/{rec['detectable']}"
          f" = {rec['recall']:.2f}")
    assert rec["recall"] >= 0.7
    print("OK")


if __name__ == "__main__":
    main()
