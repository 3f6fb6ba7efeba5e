"""Algorithmic work of one pool step, from shapes, and the chips' peaks.

The counts follow the algorithm, not its implementation: the same number
whatever program computes the step. Per station and block of ``n``
fingerprints over ``n_frames`` STFT frames:

FLOPs (matrix work, the only work with a published peak):
  STFT      n_frames · (L + 2 · 2 L K + 3 K)   window, two DFT matmuls, power
  pooling   n_frames · 2 K F                  band bins → img_freq
  Haar      n · 2 (F² T + T² F)               the two transform matmuls

Min-Max compares (no published peak; printed beside the bound):
  n · top_k · n_hash_fns · 2                  min and max over the set bits

Bytes the step must touch:
  samples   4 · new samples                   the block's new waveform
  expire    2 · 4 · t B C                     read and write of the ids table
  insert    n t (2 · 2 · 4 C + 2 · 2 · 4)     bucket rows of sig and ids,
                                              cursor and traffic, read + write
  query     n t (2 · 4 C + 4)                 bucket rows of sig, ids, traffic
  limiter   n t · 2 · 4 C + 2 · 4 n           bucket rows again, occ slots
  pk        4 W n + 2 · 4 W P                 packed rows written, both
                                              endpoints of P pairs read
  pairs out 16 P                              idx1, idx2, sim, jaccard

with L = stft_len, K = band bins, F = img_freq, T = img_time, t tables,
B buckets, C slots per bucket, W = fp_dim / 32 words and P the pairs the
block emitted.
"""
from __future__ import annotations

import math

# Published peaks per chip, keyed by ``device_kind`` as JAX reports it.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/work.py PEAKS")
    return PEAKS[device_kind]


def _band(fp: dict) -> int:
    n_rfft = fp["stft_len"] // 2 + 1
    lo = int(math.ceil(fp["band_lo_hz"] * fp["stft_len"] / fp["fs"]))
    hi = int(math.floor(fp["band_hi_hz"] * fp["stft_len"] / fp["fs"])) + 1
    lo = max(0, min(lo, n_rfft - 1))
    return max(lo + 1, min(hi, n_rfft)) - lo


def station_step(fp: dict, lsh: dict, index: dict, n: int,
                 pairs: float = 0.0) -> dict:
    """Work of one station's block of ``n`` fingerprints emitting
    ``pairs`` pairs: {"flops", "compares", "bytes"}."""
    L, hop = fp["stft_len"], fp["stft_hop"]
    F, T = fp["img_freq"], fp["img_time"]
    K = _band(fp)
    lag = fp["img_hop"] * hop
    block_samples = (n - 1) * lag + (T - 1) * hop + L
    n_frames = (block_samples - L) // hop + 1
    stft = n_frames * (L + 2 * 2 * L * K + 3 * K)
    pool = n_frames * 2 * K * F
    haar = n * 2 * (F * F * T + T * T * F)
    t = lsh["n_tables"]
    n_fns = t * (lsh["n_funcs"] // 2 if lsh["use_minmax"] else lsh["n_funcs"])
    compares = n * fp["top_k"] * n_fns * 2
    B, C = index["n_buckets"], index["bucket_cap"]
    W = 2 * F * T // 32
    nbytes = (4 * n * lag
              + 2 * 4 * t * B * C
              + n * t * (2 * 2 * 4 * C + 2 * 2 * 4)
              + n * t * (2 * 4 * C + 4)
              + n * t * 2 * 4 * C + 2 * 4 * n
              + 4 * W * n + 2 * 4 * W * pairs
              + 16 * pairs)
    return {"flops": float(stft + pool + haar), "compares": float(compares),
            "bytes": float(nbytes)}


def least_time(work: dict, peak: dict) -> tuple[float, str]:
    """(seconds, bound) — the larger of FLOPs over peak FLOP/s and bytes
    over HBM bandwidth, and which of the two it is."""
    tf = work["flops"] / peak["flops"]
    tb = work["bytes"] / peak["hbm_bytes_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
