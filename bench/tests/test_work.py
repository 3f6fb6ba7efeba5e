"""Work counts of one station's step, against arithmetic done by hand at
the program's smoke shapes (16 x 32 images, hop 8, top_k 64, t = 20
tables of k = 4, 2048 buckets of 8, blocks of 64 fingerprints)."""
import pytest

from bench import work

FP = {"fs": 100.0, "stft_len": 200, "stft_hop": 25, "band_lo_hz": 3.0,
      "band_hi_hz": 20.0, "img_freq": 16, "img_time": 32, "img_hop": 8,
      "top_k": 64}
LSH = {"n_tables": 20, "n_funcs": 4, "use_minmax": True}
INDEX = {"n_buckets": 2048, "bucket_cap": 8}


def test_smoke_shapes_by_hand():
    w = work.station_step(FP, LSH, INDEX, n=64, pairs=10)
    # block: 63 lags of 200 + a 975-sample window = 13575 samples,
    # (13575 - 200) / 25 + 1 = 536 frames; band bins 6..40 → K = 35
    stft = 536 * (200 + 4 * 200 * 35 + 3 * 35)           # 15 171 480
    pool = 536 * 2 * 35 * 16                              # 600 320
    haar = 64 * 2 * (16 * 16 * 32 + 32 * 32 * 16)         # 3 145 728
    assert w["flops"] == stft + pool + haar == 18_917_528
    assert w["compares"] == 64 * 64 * 40 * 2 == 327_680
    nbytes = (51_200            # 64 x 200 new samples x 4 B
              + 2_621_440       # ids table 20 x 2048 x 8 x 4 B, read + write
              + 184_320         # insert: 64 x 20 x (2 x 2 x 32 B + 2 x 2 x 4 B)
              + 87_040          # query: 64 x 20 x (2 x 32 B + 4 B)
              + 82_432          # limiter: 64 x 20 x 64 B + 2 x 4 x 64 B
              + 8_192           # pk rows written: 64 x 32 words x 4 B
              + 2_560           # pk rows read: 2 x 10 pairs x 128 B
              + 160)            # pairs out: 10 x 16 B
    assert w["bytes"] == nbytes == 3_037_344


def test_least_time_names_its_bound():
    w = work.station_step(FP, LSH, INDEX, n=64, pairs=10)
    t, bound = work.least_time(w, work.peaks("TPU v5 lite"))
    assert bound == "bytes"
    assert t == pytest.approx(3_037_344 / 819e9)
    t, bound = work.least_time({"flops": 197e12, "bytes": 1.0},
                               work.peaks("TPU v5 lite"))
    assert (t, bound) == (1.0, "flops")


def test_paper_expire_scan():
    # the paper index: 100 x 16384 x 8 int32 ids, read and written
    w = work.station_step({**FP, "img_freq": 32, "img_time": 128,
                           "top_k": 400},
                          {"n_tables": 100, "n_funcs": 8, "use_minmax": True},
                          {"n_buckets": 16384, "bucket_cap": 8}, n=16)
    assert w["bytes"] > 2 * 4 * 100 * 16384 * 8 == 104_857_600


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
