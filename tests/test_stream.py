"""Streaming detection subsystem: index semantics, ingest halo exactness,
offline/streaming parity (incl. golden pin), fused single-dispatch hot
path (parity / retracing / donation guards), bounded sliding-window mode
with cross-window merge, snapshot/restore, serving smoke."""
import dataclasses
import json
import pathlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.fast_seismic import (smoke_config,
                                        stream_bounded_smoke_config,
                                        stream_compact_smoke_config,
                                        stream_deferred_smoke_config,
                                        stream_smoke_config)
from repro.core import fingerprint as F
from repro.core import lsh as L
from repro.core.align import AlignConfig
from repro.core.detect import DetectConfig
from repro.core.lsh import INVALID, LSHConfig
from repro.core.synth import SynthConfig, make_dataset
from repro.stream import (StreamConfig, StreamingDetector, StreamIndexConfig,
                          WaveformRing)
from repro.stream import fused as FU
from repro.stream import index as SI
from repro.stream.engine import (RollingPairFilter, merge_boundary_rows,
                                 stream_step)
from repro.stream.ingest import StreamingMAD

CFG = LSHConfig(n_tables=20, n_funcs=4, n_matches=2, bucket_cap=8,
                min_dt=1, occurrence_frac=0.0)


def _random_sigs(rng, n, t=CFG.n_tables):
    return jnp.asarray(rng.integers(0, 2**32, (n, t), dtype=np.uint32))


# ---------------------------------------------------------------------------
# StreamingIndex unit semantics
# ---------------------------------------------------------------------------


def test_index_insert_query_roundtrip(rng):
    icfg = StreamIndexConfig(n_buckets=256, bucket_cap=4)
    state = SI.init_index(CFG, icfg)
    sigs = _random_sigs(rng, 16)
    # duplicate signatures → guaranteed collisions in every table
    sigs = sigs.at[12].set(sigs[3])
    ids = jnp.arange(16, dtype=jnp.int32)
    state = SI.insert(state, sigs, ids, CFG)
    pairs = SI.query(state, sigs, ids, CFG)
    v = np.asarray(pairs.valid)
    found = set(zip(np.asarray(pairs.idx1)[v].tolist(),
                    np.asarray(pairs.idx2)[v].tolist()))
    assert (3, 12) in found
    sims = np.asarray(pairs.sim)[v]
    got = {p: s for p, s in zip(found, sims)}
    assert got[(3, 12)] == CFG.n_tables  # collided in every table
    # random signatures should not pair up
    assert len(found) == 1


def test_index_cross_batch_pairs_and_id_order(rng):
    state = SI.init_index(CFG, StreamIndexConfig(n_buckets=256, bucket_cap=4))
    s1 = _random_sigs(rng, 8)
    s2 = _random_sigs(rng, 8)
    s2 = s2.at[5].set(s1[2])      # batch-2 row matches batch-1 row
    state = SI.insert(state, s1, jnp.arange(8, dtype=jnp.int32), CFG)
    pairs1 = SI.query(state, s1, jnp.arange(8, dtype=jnp.int32), CFG)
    state = SI.insert(state, s2, 8 + jnp.arange(8, dtype=jnp.int32), CFG)
    pairs2 = SI.query(state, s2, 8 + jnp.arange(8, dtype=jnp.int32), CFG)
    v2 = np.asarray(pairs2.valid)
    found = set(zip(np.asarray(pairs2.idx1)[v2].tolist(),
                    np.asarray(pairs2.idx2)[v2].tolist()))
    assert found == {(2, 13)}
    assert int(np.asarray(pairs1.valid).sum()) == 0


def test_index_min_dt_exclusion(rng):
    cfg = L.LSHConfig(n_tables=8, n_funcs=4, n_matches=1, bucket_cap=8,
                      min_dt=4, occurrence_frac=0.0)
    state = SI.init_index(cfg, StreamIndexConfig(n_buckets=64, bucket_cap=8))
    sigs = jnp.tile(_random_sigs(rng, 1, t=8), (6, 1))   # all identical
    ids = jnp.arange(6, dtype=jnp.int32)
    state = SI.insert(state, sigs, ids, cfg)
    pairs = SI.query(state, sigs, ids, cfg)
    v = np.asarray(pairs.valid)
    dts = (np.asarray(pairs.idx2) - np.asarray(pairs.idx1))[v]
    assert (dts >= 4).all() and v.sum() > 0


def test_index_ring_eviction(rng):
    """A bucket holds at most cap entries; oldest get evicted."""
    cfg = L.LSHConfig(n_tables=4, n_funcs=4, n_matches=1, bucket_cap=8,
                      min_dt=1, occurrence_frac=0.0)
    state = SI.init_index(cfg, StreamIndexConfig(n_buckets=64, bucket_cap=2))
    sig = _random_sigs(rng, 1, t=4)
    for i in range(5):            # same signature, five separate inserts
        state = SI.insert(state, sig, jnp.asarray([i], jnp.int32), cfg)
    pairs = SI.query(state, sig, jnp.asarray([5], jnp.int32), cfg)
    v = np.asarray(pairs.valid)
    partners = np.asarray(pairs.idx1)[v]
    # only the 2 newest residents can pair (ids 3 and 4)
    assert set(partners.tolist()) == {3, 4}
    st = SI.index_stats(state)
    assert st["max_bucket_fill"] <= 2
    assert st["inserted"] == 5


def test_index_expire_sliding_window(rng):
    state = SI.init_index(CFG, StreamIndexConfig(n_buckets=256, bucket_cap=4))
    sigs = _random_sigs(rng, 8)
    state = SI.insert(state, sigs, jnp.arange(8, dtype=jnp.int32), CFG)
    state = SI.expire(state, 5)
    resident = np.asarray(state.ids)
    assert (resident[resident != INVALID] >= 5).all()
    # expired entries no longer pair
    pairs = SI.query(state, sigs, 100 + jnp.arange(8, dtype=jnp.int32), CFG)
    v = np.asarray(pairs.valid)
    assert (np.asarray(pairs.idx1)[v] >= 5).all()


def test_index_valid_mask_not_stored(rng):
    state = SI.init_index(CFG, StreamIndexConfig(n_buckets=256, bucket_cap=4))
    sigs = _random_sigs(rng, 8)
    valid = jnp.asarray([True] * 4 + [False] * 4)
    state = SI.insert(state, sigs, jnp.arange(8, dtype=jnp.int32), CFG,
                      valid=valid)
    assert SI.index_stats(state)["resident"] == 4 * CFG.n_tables


def _ring_insert_model(m, sigs, ids, valid, buckets):
    """Plain ring buffers: each valid row, in batch order, takes its
    bucket's next position mod C in every table; later rows overwrite."""
    c = m["sig"].shape[2]
    for i in np.flatnonzero(valid):
        for tb, bk in enumerate(buckets[i]):
            p = m["cursor"][tb, bk] % c
            m["sig"][tb, bk, p] = sigs[i, tb]
            m["ids"][tb, bk, p] = ids[i]
            m["cursor"][tb, bk] += 1
            m["traffic"][tb, bk] += 1
    m["inserted"] += int(valid.sum())


@pytest.mark.parametrize("t,b,c,n,invalid,cursor0", [
    (1, 4, 4, 32, 0.0, 0),       # bucket runs longer than C evict in-batch
    (3, 16, 4, 24, 0.35, 0),     # invalid rows never land
    (2, 8, 4, 16, 0.0, 997),     # cursors far past C wrap the ring
    (20, 64, 8, 64, 0.2, 5),     # many tables at once
], ids=["run_over_cap", "invalid_rows", "cursor_wraps", "many_tables"])
def test_index_insert_matches_ring_model(rng, t, b, c, n, invalid, cursor0):
    """The (bucket, position) scatter equals per-bucket ring buffers, bit
    for bit, over several batches."""
    cfg = L.LSHConfig(n_tables=t, n_funcs=4, n_matches=1, bucket_cap=8,
                      min_dt=1, occurrence_frac=0.0)
    state = SI.init_index(cfg, StreamIndexConfig(n_buckets=b, bucket_cap=c))
    cursor = rng.integers(cursor0, cursor0 + c + 1, (t, b)).astype(np.int32)
    state = dataclasses.replace(state, cursor=jnp.asarray(cursor))
    m = {k: np.array(getattr(state, k))
         for k in ("sig", "ids", "cursor", "traffic", "inserted")}
    for k in range(4):
        sigs = rng.integers(0, 2**32, (n, t), dtype=np.uint32)
        ids = np.arange(k * n, (k + 1) * n, dtype=np.int32)
        valid = rng.random(n) >= invalid
        buckets = rng.integers(0, b, (n, t)).astype(np.int32)
        state = SI.insert(state, jnp.asarray(sigs), jnp.asarray(ids), cfg,
                          valid=jnp.asarray(valid),
                          buckets=jnp.asarray(buckets))
        _ring_insert_model(m, sigs, ids, valid, buckets)
    for k, want in m.items():
        np.testing.assert_array_equal(np.asarray(getattr(state, k)), want,
                                      err_msg=k)


# ---------------------------------------------------------------------------
# in-dispatch §6.5 occurrence limiter + window-relative saturation (ISSUE 5)
# ---------------------------------------------------------------------------


def _guarded_batch(state, sigs, base, cfg, n_buckets, **kw):
    n = sigs.shape[0]
    buckets = L.bucket_ids(sigs, n_buckets, cfg.seed)
    ids = base + jnp.arange(n, dtype=jnp.int32)
    return SI.guarded_step(state, sigs, buckets, ids, None, cfg, **kw)


def test_occ_limiter_quarantines_dense_repeaters(rng):
    """A fingerprint family colliding in every table (glitch-train shape)
    accumulates raw partner collisions past the limit within its very
    first batch — in-step counting, so even the first block's pairs die —
    and stays quarantined; sparse random batches through the same limiter
    are bit-identical to the limiter-off program."""
    cfg = L.LSHConfig(n_tables=8, n_funcs=4, n_matches=1, bucket_cap=8,
                      min_dt=1, occurrence_frac=0.0)
    icfg = StreamIndexConfig(n_buckets=256, bucket_cap=8, occ_slots=512)
    glitch = jnp.tile(_random_sigs(rng, 1, t=8), (4, 1))   # identical sigs
    emitted, limited = [], 0
    state = SI.init_index(cfg, icfg)
    for step in range(4):
        state, pairs, qc = _guarded_batch(state, glitch, jnp.int32(4 * step),
                                          cfg, 256, window=0, occ_limit=20)
        emitted.append(int(np.asarray(pairs.valid).sum()))
        limited += int(np.asarray(qc)[2])
    assert sum(emitted) == 0             # never a single train pair out
    assert limited > 0                   # …because the limiter dropped them
    assert int(np.asarray(state.occ).max()) > 20
    # a sparse batch through the same limiter config is untouched
    state2 = SI.init_index(cfg, icfg)
    sparse = _random_sigs(rng, 8, t=8)
    state2, p1, qc1 = _guarded_batch(state2, sparse, jnp.int32(0), cfg, 256,
                                     window=0, occ_limit=20)
    state3 = SI.init_index(cfg, icfg)
    state3, p0, _ = _guarded_batch(state3, sparse, jnp.int32(0), cfg, 256,
                                   window=0, occ_limit=0)
    np.testing.assert_array_equal(np.asarray(p1.valid), np.asarray(p0.valid))
    assert int(np.asarray(qc1)[2]) == 0


def test_occ_limiter_ring_recycles_with_stream():
    """Partner counts die as the id stream advances past the ring span
    (the expire-coupled decay): a fingerprint family quarantined early
    emits again once its counts have been recycled."""
    rng = np.random.default_rng(1)
    cfg = L.LSHConfig(n_tables=8, n_funcs=4, n_matches=1, bucket_cap=8,
                      min_dt=1, occurrence_frac=0.0)
    icfg = StreamIndexConfig(n_buckets=256, bucket_cap=8, occ_slots=32)
    window = 16
    sig = jnp.asarray(rng.integers(0, 2**32, (1, 8), dtype=np.uint32))
    dense = jnp.tile(sig, (4, 1))
    state = SI.init_index(cfg, icfg)
    # batch 1 emits (intra-batch counts under the limit); batch 2's rows
    # also hit batch 1's residents, cross the limit, and are quarantined
    state, p0, _ = _guarded_batch(state, dense, jnp.int32(0), cfg, 256,
                                  window=window, occ_limit=30)
    assert int(np.asarray(p0.valid).sum()) > 0
    state, p1, _ = _guarded_batch(state, dense, jnp.int32(4), cfg, 256,
                                  window=window, occ_limit=30)
    assert int(np.asarray(p1.valid).sum()) == 0
    # a full ring of unrelated ids later, the family's slots recycled
    # (and the window expired the old residents): emission resumes
    base = 8
    for k in range(8):
        filler = jnp.asarray(rng.integers(0, 2**32, (4, 8), dtype=np.uint32))
        state, _, _ = _guarded_batch(state, filler, jnp.int32(base + 4 * k),
                                     cfg, 256, window=window, occ_limit=30)
    state, p2, _ = _guarded_batch(state, dense, jnp.int32(base + 32), cfg,
                                  256, window=window, occ_limit=30)
    assert int(np.asarray(p2.valid).sum()) > 0


def test_occ_limit_requires_ring():
    """The limiter without a partner-count ring is a config error, caught
    up front (not a silent (1,)-ring that quarantines everything)."""
    with pytest.raises(ValueError, match="occ_slots"):
        StreamConfig(occ_limit=10)
    # a ring narrower than the sliding window would alias live counters
    with pytest.raises(ValueError, match="narrower"):
        StreamConfig(occ_limit=10, window_fingerprints=8192,
                     index=StreamIndexConfig(occ_slots=1024))
    # and the dirty smoke config carries a properly sized ring
    from repro.configs.fast_seismic import stream_dirty_smoke_config
    scfg = stream_dirty_smoke_config()
    assert scfg.occ_limit > 0 and scfg.index.occ_slots >= 4096


def test_saturation_traffic_decays_with_window():
    """Window-relative saturation (the ROADMAP follow-up): a bucket
    quarantined by a traffic burst recovers after the sliding window
    passes (its counter halves per window), unlike the old lifetime
    counter which never forgave."""
    rng = np.random.default_rng(2)
    cfg = L.LSHConfig(n_tables=4, n_funcs=4, n_matches=1, bucket_cap=8,
                      min_dt=1, occurrence_frac=0.0)
    icfg = StreamIndexConfig(n_buckets=64, bucket_cap=8)
    window = 16
    sig = jnp.asarray(rng.integers(0, 2**32, (1, 4), dtype=np.uint32))
    dense = jnp.tile(sig, (4, 1))
    state = SI.init_index(cfg, icfg)
    # hammer one bucket family past the saturation limit
    for step in range(4):
        state, pairs, qc = _guarded_batch(
            state, dense, jnp.int32(4 * step), cfg, 64,
            window=window, saturation=10)
    assert int(np.asarray(qc)[1]) > 0            # quarantine engaged
    assert int(np.asarray(pairs.valid).sum()) == 0
    hot_before = int(np.asarray(state.traffic).max())
    assert hot_before > 10
    # the glitching channel is "repaired": several windows of benign
    # traffic later the counter has halved back under the limit
    base = 16
    for k in range(8):
        filler = jnp.asarray(rng.integers(0, 2**32, (4, 4), dtype=np.uint32))
        state, _, _ = _guarded_batch(state, filler, jnp.int32(base + 4 * k),
                                     cfg, 64, window=window, saturation=10)
    assert int(np.asarray(state.traffic).max()) <= 10
    # the family pairs again (its old residents expired; new inserts are
    # below the limit)
    state, p2, _ = _guarded_batch(state, dense, jnp.int32(base + 32), cfg,
                                  64, window=window, saturation=10)
    assert int(np.asarray(p2.valid).sum()) > 0
    # lifetime behavior (window=0) keeps the quarantine forever
    state_l = SI.init_index(cfg, icfg)
    for step in range(4):
        state_l, _, _ = _guarded_batch(state_l, dense, jnp.int32(4 * step),
                                       cfg, 64, window=0, saturation=10)
    for k in range(8):
        filler = jnp.asarray(rng.integers(0, 2**32, (4, 4), dtype=np.uint32))
        state_l, _, _ = _guarded_batch(state_l, filler,
                                       jnp.int32(16 + 4 * k), cfg, 64,
                                       window=0, saturation=10)
    assert int(np.asarray(state_l.traffic).max()) > 10


# ---------------------------------------------------------------------------
# ingest: ring framing + halo exactness + reservoir stats
# ---------------------------------------------------------------------------


def test_ring_blocks_are_sample_exact(rng):
    fcfg = F.FingerprintConfig(img_freq=16, img_time=32, img_hop=8, top_k=64,
                               mad_sample_rate=1.0)
    wf = rng.standard_normal(30_000).astype(np.float32)
    ring = WaveformRing(fcfg, block_fingerprints=16)
    blocks = []
    for chunk in np.array_split(wf, 7):   # uneven chunk lengths
        blocks.extend(ring.push(chunk))
    tail = ring.flush_partial()
    coeffs_off = np.asarray(F.coeffs_from_waveform(jnp.asarray(wf), fcfg))
    got = 0
    for base, blk, mask in blocks:
        assert mask is None               # contiguous input: all valid
        cb = np.asarray(F.coeffs_from_waveform(jnp.asarray(blk), fcfg))
        np.testing.assert_allclose(cb, coeffs_off[base: base + 16],
                                   rtol=1e-5, atol=1e-5)
        got += cb.shape[0]
    assert tail is not None
    base, blk, mask = tail
    n_valid = int(mask.sum())
    assert mask[:n_valid].all()           # clean tail mask is a prefix
    cb = np.asarray(F.coeffs_from_waveform(jnp.asarray(blk), fcfg))[:n_valid]
    np.testing.assert_allclose(cb, coeffs_off[base: base + n_valid],
                               rtol=1e-5, atol=1e-5)
    assert got + n_valid == fcfg.n_fingerprints(wf.size)


def test_streaming_mad_matches_full_sample(rng):
    coeffs = rng.standard_normal((200, 32)).astype(np.float32)
    sm = StreamingMAD(n_rows=400, n_coeff=32, seed=0)   # reservoir > rows
    for part in np.array_split(coeffs, 9):
        sm.update(part)
    med, mad = sm.stats()
    np.testing.assert_allclose(med, np.median(coeffs, axis=0), atol=1e-6)
    np.testing.assert_allclose(
        mad, np.median(np.abs(coeffs - np.median(coeffs, 0)[None]), 0),
        atol=1e-6)
    # capped reservoir keeps exactly n_rows with uniform-ish coverage
    sm2 = StreamingMAD(n_rows=64, n_coeff=32, seed=0)
    for part in np.array_split(coeffs, 9):
        sm2.update(part)
    assert sm2.filled == 64 and sm2.seen == 200


# ---------------------------------------------------------------------------
# parity: streamed chunks == offline search (acceptance criterion)
# ---------------------------------------------------------------------------


def _parity_setup():
    cfg = smoke_config()
    ds = make_dataset(SynthConfig(duration_s=600.0, n_stations=1,
                                  n_sources=2, events_per_source=5,
                                  event_snr=3.0, seed=3))
    wf = ds.waveforms[0]
    fcfg = cfg.fingerprint
    bits, _ = F.fingerprints_from_waveform(jnp.asarray(wf), fcfg,
                                           key=jax.random.PRNGKey(0))
    pairs_off, _ = L.search(bits, cfg.lsh)
    v = np.asarray(pairs_off.valid)
    off = set(zip(np.asarray(pairs_off.idx1)[v].tolist(),
                  np.asarray(pairs_off.idx2)[v].tolist()))
    med_mad = F.mad_stats(F.coeffs_from_waveform(jnp.asarray(wf), fcfg),
                          1.0, jax.random.PRNGKey(0))
    return cfg, wf, off, (np.asarray(med_mad[0]), np.asarray(med_mad[1]))


def _stream_pairs(cfg, wf, n_chunks, med_mad=None, scfg=None):
    scfg = scfg or StreamConfig(
        block_fingerprints=64,
        index=StreamIndexConfig(n_buckets=2048, bucket_cap=8),
        stats_warmup_blocks=2)
    det = StreamingDetector(cfg, scfg, n_stations=1, med_mad=med_mad)
    for chunk in np.array_split(wf, n_chunks):
        det.push(chunk)
    events, pairs, fstats = det.stations[0].finalize()
    v = np.asarray(pairs.valid)
    got = set(zip(np.asarray(pairs.idx1)[v].tolist(),
                  np.asarray(pairs.idx2)[v].tolist()))
    return got, fstats, det


@pytest.mark.slow
def test_streaming_parity_with_offline_search():
    """≥95% of offline pairs recovered from ≥8 chunks, no spurious blowup."""
    cfg, wf, off, med_mad = _parity_setup()
    got, fstats, det = _stream_pairs(cfg, wf, n_chunks=10, med_mad=med_mad)
    assert len(off) > 0
    recovered = len(off & got) / len(off)
    assert recovered >= 0.95, (recovered, len(off), len(got))
    assert len(got - off) <= max(2, int(0.1 * len(off))), (got - off)
    # event counts must not blow up vs the offline pair population
    assert fstats["events"] <= max(4, 2 * len(off))


@pytest.mark.slow
def test_streaming_parity_self_stats():
    """Self-computed reservoir statistics stay close to offline results."""
    cfg, wf, off, _ = _parity_setup()
    got, fstats, _ = _stream_pairs(cfg, wf, n_chunks=10)
    recovered = len(off & got) / max(len(off), 1)
    assert recovered >= 0.7, (recovered, len(off), len(got))
    assert len(got - off) <= max(3, len(off))
    assert fstats["events"] <= 2 * max(2, len(off))


GOLDEN = pathlib.Path(__file__).parent / "golden" / "stream_pairs.json"


def test_streaming_golden_pair_parity():
    """Golden pin: fixed-seed trace, expected pair sets under tests/golden/.

    Two-pass stats must reproduce the stored streamed pair set *exactly*
    (and with it 100% recovery of the stored offline set); self-computed
    reservoir stats with the default warmup must stay at or above the
    recorded ~88% recovery; and the re-binarize-after-freeze hook
    (``stats_warmup_blocks=0``: the reservoir absorbs the whole trace
    before the flush-time freeze binarizes the buffered warmup blocks)
    must close that gap completely — the deferred-freeze self-computed
    statistics reproduce the two-pass pair set exactly, 100% offline
    recall. Any parity drift fails loudly here instead of sliding under
    the slow threshold tests.
    """
    gold = json.loads(GOLDEN.read_text())
    cfg = smoke_config()
    ds = make_dataset(SynthConfig(**gold["synth"]))
    wf = ds.waveforms[0]
    fcfg = cfg.fingerprint
    med_mad = F.mad_stats(F.coeffs_from_waveform(jnp.asarray(wf), fcfg),
                          1.0, jax.random.PRNGKey(0))
    med_mad = (np.asarray(med_mad[0]), np.asarray(med_mad[1]))
    off = {tuple(p) for p in gold["offline_pairs"]}
    expect_two = {tuple(p) for p in gold["stream_two_pass_pairs"]}

    got_two, _, _ = _stream_pairs(cfg, wf, gold["n_chunks"],
                                  med_mad=med_mad)
    assert got_two == expect_two, (
        sorted(got_two - expect_two), sorted(expect_two - got_two))
    assert len(off & got_two) == len(off)      # 100% of offline recovered

    got_self, _, _ = _stream_pairs(cfg, wf, gold["n_chunks"])
    recovered = len(off & got_self) / len(off)
    floor = gold["self_stats_recall"] - 0.03   # small slack under the pin
    assert recovered >= floor, (recovered, gold["self_stats_recall"])

    # deferred freeze: self-computed stats == offline two-pass stats
    got_def, _, _ = _stream_pairs(cfg, wf, gold["n_chunks"],
                                  scfg=stream_deferred_smoke_config())
    assert got_def == expect_two, (
        sorted(got_def - expect_two), sorted(expect_two - got_def))
    assert len(off & got_def) == len(off)      # gap closed: 100% recall

    # ISSUE 8: compacted emission + exact-Jaccard verify reproduces the
    # golden pair set bit-exactly (the bound sits above every real
    # per-block pair count, so nothing overflows on clean data)
    got_cmp, _, det_cmp = _stream_pairs(cfg, wf, gold["n_chunks"],
                                        med_mad=med_mad,
                                        scfg=stream_compact_smoke_config())
    assert got_cmp == expect_two, (
        sorted(got_cmp - expect_two), sorted(expect_two - got_cmp))
    assert det_cmp.telemetry.drop_breakdown()["overflow_pairs"] == 0


def test_streaming_with_station_stats_equals_backfill():
    """Given the offline per-station statistics (``station_stats``, one
    row per station), the streaming pool and the batch replay run the
    same binarization per station: their per-station pair sets are
    identical and non-empty. Mismatched rows are rejected."""
    from repro.core.detect import detect_events, station_stats
    cfg, scfg = smoke_config(), stream_compact_smoke_config()
    ds = make_dataset(SynthConfig(duration_s=600.0, n_stations=2,
                                  n_sources=2, events_per_source=5,
                                  event_snr=3.0, seed=3))
    med, mad = station_stats(ds.waveforms, cfg.fingerprint)
    assert med.shape == mad.shape == (2, cfg.fingerprint.n_coeff)
    det = StreamingDetector(cfg, scfg, n_stations=2, med_mad=(med, mad))
    for chunk in np.array_split(ds.waveforms, 10, axis=1):
        det.push(chunk)
    _, _, _, stats = detect_events(ds.waveforms, cfg, scfg=scfg,
                                   keep_pairs=True)

    def as_set(p):
        v = np.asarray(p.valid)
        return set(zip(np.asarray(p.idx1)[v].tolist(),
                       np.asarray(p.idx2)[v].tolist()))

    streamed = [as_set(st.finalize()[1]) for st in det.stations]
    assert streamed == [as_set(p) for p in stats["_station_pairs"]]
    assert all(streamed)
    with pytest.raises(ValueError, match="per-station med_mad"):
        StreamingDetector(cfg, scfg, n_stations=3, med_mad=(med, mad))


# ---------------------------------------------------------------------------
# emission epilogue (ISSUE 8): compaction, overflow, verify ring
# ---------------------------------------------------------------------------


def test_compact_pairs_deterministic_overflow(rng):
    """Overflow drops are deterministic and counted: the compaction keeps
    the first ``max_pairs`` valid stream positions (the lexicographically
    smallest (idx1, idx2), since the stream is pair-sorted) and reports
    exactly the surplus — identically on every run."""
    m = 64
    valid = np.zeros(m, bool)
    valid[[3, 7, 10, 21, 40, 41, 59]] = True
    pairs = L.Pairs(idx1=jnp.arange(m, dtype=jnp.int32),
                    idx2=jnp.arange(m, 2 * m, dtype=jnp.int32),
                    sim=jnp.full((m,), 5, jnp.int32),
                    valid=jnp.asarray(valid))
    outs = [SI.compact_pairs(pairs, 4) for _ in range(2)]
    for compact, overflow in outs:
        kept = np.asarray(compact.valid)
        assert int(kept.sum()) == 4
        assert int(overflow) == 3
        # first four valid stream positions survive
        assert sorted(np.asarray(compact.idx1)[kept].tolist()) \
            == [3, 7, 10, 21]
    a, b = outs
    assert np.array_equal(np.asarray(a[0].idx1), np.asarray(b[0].idx1))
    # bound above the valid count: everything kept, zero overflow
    all_kept, overflow = SI.compact_pairs(pairs, 16)
    assert int(overflow) == 0
    assert int(np.asarray(all_kept.valid).sum()) == 7


def test_stream_overflow_counted_and_deterministic():
    """A bound below the real per-block pair count drops deterministically
    and reconciles: dense emission − compacted emission = the registry's
    ``step_overflow_pairs_total`` (mirrored from the in-dispatch QC
    vector), and two runs of the starved config emit identical pairs."""
    cfg = smoke_config()
    ds = make_dataset(SynthConfig(duration_s=240.0, n_stations=1,
                                  n_sources=2, events_per_source=6,
                                  event_snr=4.0, seed=13))
    wf = ds.waveforms[0]

    def run(scfg):
        det = StreamingDetector(cfg, scfg, n_stations=1)
        for chunk in np.array_split(wf, 6):
            det.push(chunk)
        det.flush()
        tri = det.stations[0].accumulated_pairs()
        v = np.asarray(tri.valid)
        got = set(zip(np.asarray(tri.idx1)[v].tolist(),
                      np.asarray(tri.idx2)[v].tolist()))
        return got, det

    dense, _ = run(stream_smoke_config())
    starved = dataclasses.replace(stream_compact_smoke_config(),
                                  max_pairs_per_block=1)
    got1, det1 = run(starved)
    got2, det2 = run(starved)
    assert got1 == got2                      # deterministic drop rule
    assert got1 <= dense                     # never invents pairs
    overflow = det1.telemetry.drop_breakdown()["overflow_pairs"]
    assert overflow == det2.telemetry.drop_breakdown()["overflow_pairs"]
    assert len(dense) - len(got1) == overflow, \
        (len(dense), len(got1), overflow)
    assert overflow > 0                      # the bound actually bit


def test_compact_snapshot_restores_packed_ring(tmp_path):
    """Mid-stream snapshot under the verify config: the bit-packed
    fingerprint ring restores bit-exactly and the resumed stream emits
    the uninterrupted stream's pairs."""
    cfg, scfg = smoke_config(), stream_compact_smoke_config()
    ds = make_dataset(SynthConfig(duration_s=600.0, n_stations=1,
                                  n_sources=2, events_per_source=5,
                                  event_snr=3.0, seed=9))
    chunks = np.array_split(ds.waveforms[:1], 8, axis=1)

    det = StreamingDetector(cfg, scfg, n_stations=1)
    for c in chunks[:5]:
        det.push(c)
    det.snapshot(str(tmp_path), step=5)
    pk_before = np.asarray(jax.device_get(det.stations[0].state.pk))
    assert pk_before.any()       # the ring has really been written
    for c in chunks[5:]:
        det.push(c)

    det2, step = StreamingDetector.restore(str(tmp_path), cfg, scfg)
    assert step == 5
    pk_after = np.asarray(jax.device_get(det2.stations[0].state.pk))
    assert np.array_equal(pk_before, pk_after)
    for c in chunks[5:]:
        det2.push(c)
    e0, p0, f0 = det.stations[0].finalize()
    e1, p1, f1 = det2.stations[0].finalize()
    np.testing.assert_array_equal(np.asarray(p0.idx1), np.asarray(p1.idx1))
    np.testing.assert_array_equal(np.asarray(p0.valid),
                                  np.asarray(p1.valid))
    assert f0 == f1

    # layout guard: restoring a verify snapshot without verify is rejected
    with pytest.raises(ValueError, match="verify_jaccard"):
        StreamingDetector.restore(str(tmp_path), cfg, stream_smoke_config())


def test_verify_jaccard_channel_and_threshold(rng):
    """The verify epilogue emits exact Jaccard for every surviving pair
    (identical fingerprints score 1.0) and ``verify_min_jaccard`` drops
    low-similarity hash matches in-dispatch."""
    lcfg = CFG
    icfg = StreamIndexConfig(n_buckets=256, bucket_cap=4, pk_slots=64,
                             pk_words=4)
    n = 16
    packed = jnp.asarray(rng.integers(0, 2**32, (n, 4), dtype=np.uint32))
    packed = packed.at[12].set(packed[3])     # exact repeat → Jaccard 1.0
    bits = np.unpackbits(
        np.asarray(packed).view(np.uint8), axis=1, bitorder="little")
    sigs = L.signatures(jnp.asarray(bits), L.hash_mappings(128, lcfg), lcfg)
    ids = jnp.arange(n, dtype=jnp.int32)
    buckets = L.bucket_ids(sigs, icfg.n_buckets, lcfg.seed)

    def step(min_jac):
        state = SI.init_index(lcfg, icfg)
        _, pairs, qc = SI.guarded_step(
            state, sigs, buckets, ids, None, lcfg, window=0,
            packed=packed, max_pairs=32, verify=1, min_jac=min_jac)
        return pairs

    pairs = step(0.0)
    v = np.asarray(pairs.valid)
    got = {p: j for p, j in zip(
        zip(np.asarray(pairs.idx1)[v].tolist(),
            np.asarray(pairs.idx2)[v].tolist()),
        np.asarray(pairs.jac)[v].tolist())}
    assert got[(3, 12)] == pytest.approx(1.0)
    assert all(0.0 <= j <= 1.0 for j in got.values())

    # threshold just under 1.0: only the exact repeat survives
    strict = step(0.99)
    sv = np.asarray(strict.valid)
    kept = set(zip(np.asarray(strict.idx1)[sv].tolist(),
                   np.asarray(strict.idx2)[sv].tolist()))
    assert kept == {(3, 12)}


# ---------------------------------------------------------------------------
# bounded mode: sliding window + rolling filter + incremental association
# ---------------------------------------------------------------------------


def _bounded_setup(n_stations=3, duration_s=600.0, seed=11):
    cfg, scfg = smoke_config(), stream_bounded_smoke_config()
    ds = make_dataset(SynthConfig(duration_s=duration_s,
                                  n_stations=n_stations, n_sources=2,
                                  events_per_source=5, event_snr=3.0,
                                  seed=seed))
    return cfg, scfg, ds


def test_bounded_mode_windows_and_alerts():
    """Sliding window + rolling filter: pairs respect the window, host
    triplet state stays bounded, and multi-station alerts surface before
    finalize."""
    cfg, scfg, ds = _bounded_setup()
    det = StreamingDetector(cfg, scfg, n_stations=3)
    for start in range(0, ds.waveforms.shape[1], 6000):
        det.push(ds.waveforms[:, start: start + 6000])
    # near-real-time association fired during the stream
    assert sum(a.shape[0] for a in det.alerts) >= 1
    detections, events, stats = det.finalize()
    assert stats["detections"] >= 1
    assert stats["alerts"] >= 1
    for i in range(3):
        # rolling filter closed windows and bounded the buffered pairs
        assert stats[f"station{i}_windows"] >= 2
        assert (stats[f"station{i}_peak_buffered_triplets"]
                <= 32 * scfg.filter_window_fingerprints)
        # every retained pair honored the sliding window
        st = det.stations[i]
        assert st.host_state_rows() <= st.peak_tri_rows
        rows = st.filter.all_rows()
        if rows.shape[0]:
            assert (rows[:, 0] < scfg.window_fingerprints).all()


def test_bounded_mode_expiry_caps_pair_reach():
    """With a sliding window, emitted pair dt never exceeds the window."""
    cfg, scfg, ds = _bounded_setup(n_stations=1)
    det = StreamingDetector(cfg, scfg, n_stations=1)
    st = det.stations[0]
    seen = []
    inner_add = st.filter.add
    st.filter.add = lambda tri: (seen.append(np.asarray(tri)),
                                 inner_add(tri))[1]
    for chunk in np.array_split(ds.waveforms[0], 8):
        det.push(chunk)
    st.flush()
    tri = np.concatenate(seen, axis=0)
    assert tri.shape[0] > 0
    assert ((tri[:, 1] - tri[:, 0]) < scfg.window_fingerprints).all()
    # and without a window the same trace emits farther-reaching pairs
    det2 = StreamingDetector(cfg, stream_smoke_config(), n_stations=1)
    for chunk in np.array_split(ds.waveforms[0], 8):
        det2.push(chunk)
    det2.stations[0].flush()
    tri2 = (np.concatenate(det2.stations[0].triplets, axis=0)
            if det2.stations[0].triplets else np.zeros((0, 3), np.int64))
    assert (tri2[:, 1] - tri2[:, 0]).max() >= scfg.window_fingerprints


def test_snapshot_restore_roundtrip(tmp_path):
    """Kill/restore mid-stream reproduces the uninterrupted detections
    exactly (acceptance criterion)."""
    cfg, scfg, ds = _bounded_setup()
    wf = ds.waveforms
    starts = list(range(0, wf.shape[1], 6000))
    half = len(starts) // 2

    run = StreamingDetector(cfg, scfg, n_stations=3)
    for s in starts[:half]:
        run.push(wf[:, s: s + 6000])
    run.snapshot(str(tmp_path), step=half)

    restored, step = StreamingDetector.restore(str(tmp_path), cfg, scfg)
    assert step == half
    for s in starts[half:]:
        run.push(wf[:, s: s + 6000])
        restored.push(wf[:, s: s + 6000])

    uninterrupted = StreamingDetector(cfg, scfg, n_stations=3)
    for s in starts:
        uninterrupted.push(wf[:, s: s + 6000])

    d0, _, s0 = uninterrupted.finalize()
    d1, _, s1 = run.finalize()
    d2, _, s2 = restored.finalize()
    for name in ("dt", "onset", "n_stations", "score", "valid"):
        np.testing.assert_array_equal(np.asarray(d0[name]),
                                      np.asarray(d2[name]), err_msg=name)
        np.testing.assert_array_equal(np.asarray(d0[name]),
                                      np.asarray(d1[name]), err_msg=name)
    assert s2["detections"] == s0["detections"]
    # alert history also carries across the restore
    assert (sum(a.shape[0] for a in restored.alerts)
            == sum(a.shape[0] for a in run.alerts))


def test_snapshot_restore_rejects_mode_mismatch(tmp_path):
    """Restoring under a different streaming mode fails up front with a
    clear error, not a KeyError deep in state reconstruction."""
    cfg, scfg, ds = _bounded_setup(n_stations=1, duration_s=400.0)
    det = StreamingDetector(cfg, scfg, n_stations=1)
    for chunk in np.array_split(ds.waveforms[0], 4):
        det.push(chunk)
    det.snapshot(str(tmp_path))
    with pytest.raises(ValueError, match="window_fingerprints"):
        StreamingDetector.restore(str(tmp_path), cfg, stream_smoke_config())


def test_snapshot_restore_parity_mode(tmp_path):
    """Snapshot/restore is exact in the unbounded parity mode too (the
    accumulated triplets and reservoir state travel with the index)."""
    cfg, scfg = smoke_config(), stream_smoke_config()
    ds = make_dataset(SynthConfig(duration_s=400.0, n_stations=1,
                                  n_sources=2, events_per_source=4,
                                  event_snr=3.0, seed=5))
    wf = ds.waveforms[0]
    chunks = np.array_split(wf, 8)

    run = StreamingDetector(cfg, scfg, n_stations=1)
    for c in chunks[:3]:
        run.push(c)
    run.snapshot(str(tmp_path))
    restored, _ = StreamingDetector.restore(str(tmp_path), cfg, scfg)
    for c in chunks[3:]:
        run.push(c)
        restored.push(c)
    e1, p1, f1 = run.stations[0].finalize()
    e2, p2, f2 = restored.stations[0].finalize()
    np.testing.assert_array_equal(np.asarray(p1.idx1), np.asarray(p2.idx1))
    np.testing.assert_array_equal(np.asarray(p1.valid), np.asarray(p2.valid))
    assert f1 == f2


def test_stream_step_no_retracing():
    """Same-shape chunks reuse one executable, in both hot paths.

    Unfused: ``block_coeffs`` + ``stream_step`` + insert/query caches stay
    flat. Fused: the steady state is exactly ONE ``step_advance`` trace —
    the one-dispatch invariant's retracing half (≤1 trace across ≥3
    same-shape chunks after warmup).
    """
    cfg, wf, _, med_mad = _parity_setup()
    chunks = np.array_split(wf, 10)

    # -- unfused chain
    scfg = StreamConfig(block_fingerprints=64,
                        index=StreamIndexConfig(n_buckets=512, bucket_cap=8),
                        fused=False, pooled=False)
    det = StreamingDetector(cfg, scfg, n_stations=1, med_mad=med_mad)
    st = det.stations[0]
    for c in chunks[:3]:
        det.push(c)
    blocks_before = st.stats.blocks
    traces_before = stream_step._cache_size()
    ins_before = SI.insert._cache_size()
    q_before = SI.query._cache_size()
    for c in chunks[3:]:
        det.push(c)
    assert st.stats.blocks > blocks_before   # more same-shape blocks ran
    assert stream_step._cache_size() == traces_before
    assert SI.insert._cache_size() == ins_before
    assert SI.query._cache_size() == q_before

    # -- fused single-dispatch path
    scfg_f = dataclasses.replace(scfg, fused=True)
    adv_start = FU.step_advance._cache_size()
    det = StreamingDetector(cfg, scfg_f, n_stations=1, med_mad=med_mad)
    st = det.stations[0]
    for c in chunks[:5]:        # ≥2 blocks: step_block seed + step_advance
        det.push(c)
    assert st.stats.blocks >= 2
    blocks_before = st.stats.blocks
    adv_before = FU.step_advance._cache_size()
    blk_before = FU.step_block._cache_size()
    assert adv_before - adv_start == 1  # one steady-state trace, total
    assert len(chunks[5:]) >= 3     # ≥3 same-shape chunks follow
    for c in chunks[5:]:
        det.push(c)
    assert st.stats.blocks >= blocks_before + 2
    assert FU.step_advance._cache_size() == adv_before  # ≤1 trace total
    assert FU.step_block._cache_size() == blk_before


def test_bounded_stream_step_no_retracing():
    """Expire + rolling-filter steps trigger no recompilation across
    chunks: the sliding window is a static arg (one extra trace total) and
    window closes reuse the padded merge/cluster executables — in the
    fused hot path too."""
    from repro.core import align as align_mod

    cfg, scfg, ds = _bounded_setup(n_stations=1)
    wf = ds.waveforms[0]
    fcfg = cfg.fingerprint
    med_mad = F.mad_stats(F.coeffs_from_waveform(jnp.asarray(wf), fcfg),
                          1.0, jax.random.PRNGKey(0))
    det = StreamingDetector(cfg, scfg, n_stations=1,
                            med_mad=(np.asarray(med_mad[0]),
                                     np.asarray(med_mad[1])))
    st = det.stations[0]
    chunks = np.array_split(wf, 12)
    for c in chunks[:6]:        # ≥2 blocks: step_advance is traced too
        det.push(c)
    # warmup must have closed at least one rolling window (so the filter's
    # merge/cluster executables exist) and run several expiring steps
    assert st.stats.blocks >= 2
    assert st.filter.windows_closed >= 1
    adv_traces = FU.step_advance._cache_size()
    blk_traces = FU.step_block._cache_size()
    merge_traces = align_mod.merge_channels._cache_size()
    cluster_traces = align_mod.cluster_station._cache_size()
    windows_before = st.filter.windows_closed
    for c in chunks[6:]:
        det.push(c)
    assert st.filter.windows_closed > windows_before  # more closes ran
    assert FU.step_advance._cache_size() == adv_traces
    assert FU.step_block._cache_size() == blk_traces
    assert align_mod.merge_channels._cache_size() == merge_traces
    assert align_mod.cluster_station._cache_size() == cluster_traces


# ---------------------------------------------------------------------------
# fused single-dispatch hot path (ISSUE 3): parity + donation guards
# ---------------------------------------------------------------------------


def _pair_set(det, station=0):
    _, pairs, fstats = det.stations[station].finalize()
    v = np.asarray(pairs.valid)
    return set(zip(np.asarray(pairs.idx1)[v].tolist(),
                   np.asarray(pairs.idx2)[v].tolist())), fstats


def test_fused_step_parity_with_multi_call_path():
    """The fused single dispatch is bit-identical to the unfused
    ``block_coeffs`` + ``stream_step`` chain on ``stream_smoke_config`` —
    same pair set with given stats, with self-computed warmup stats, and
    across the masked flush tail (acceptance criterion)."""
    cfg, wf, _, med_mad = _parity_setup()
    scfg_f = stream_smoke_config()
    scfg_u = dataclasses.replace(scfg_f, fused=False, pooled=False)
    for mm in (med_mad, None):
        got = {}
        for name, scfg in (("fused", scfg_f), ("unfused", scfg_u)):
            det = StreamingDetector(cfg, scfg, n_stations=1, med_mad=mm)
            for c in np.array_split(wf, 10):
                det.push(c)
            got[name], fstats = _pair_set(det)
            assert fstats["fingerprints"] > 0
        assert got["fused"] == got["unfused"], (
            mm is None, sorted(got["fused"] ^ got["unfused"]))
        assert len(got["fused"]) > 0


def test_pooled_detector_matches_sequential():
    """The vmapped station pool yields the same per-station pairs/events
    as S sequential single-station engines."""
    cfg, scfg, ds = _bounded_setup(n_stations=3)
    det_p = StreamingDetector(cfg, scfg, n_stations=3)
    det_s = StreamingDetector(cfg, dataclasses.replace(scfg, pooled=False),
                              n_stations=3)
    assert det_p.pooled and not det_s.pooled
    for start in range(0, ds.waveforms.shape[1], 6000):
        det_p.push(ds.waveforms[:, start: start + 6000])
        det_s.push(ds.waveforms[:, start: start + 6000])
    dp, ep, sp = det_p.finalize()
    ds_, es, ss = det_s.finalize()
    for i in range(3):
        for k in ("fingerprints", "pairs", "events", "windows"):
            assert sp[f"station{i}_{k}"] == ss[f"station{i}_{k}"], (i, k)
    assert sp["detections"] == ss["detections"]
    for name in ("dt", "onset", "n_stations", "score", "valid"):
        np.testing.assert_array_equal(np.asarray(dp[name]),
                                      np.asarray(ds_[name]), err_msg=name)


def test_fused_step_donation_no_new_allocations():
    """The donation half of the one-dispatch invariant: after warmup the
    steady state retains ZERO new device bytes per chunk — every state
    buffer is an in-place donated reuse (``jax.live_arrays`` delta)."""
    cfg, wf, _, med_mad = _parity_setup()
    scfg = stream_smoke_config()
    det = StreamingDetector(cfg, scfg, n_stations=1, med_mad=med_mad)
    st = det.stations[0]
    chunks = np.array_split(wf, 10)
    for c in chunks[:5]:        # compile step_block + step_advance
        det.push(c)
    assert st.stats.blocks >= 2
    jax.block_until_ready(st.fstate.index.cursor)
    n0 = len(jax.live_arrays())
    b0 = sum(a.nbytes for a in jax.live_arrays())
    blocks_before = st.stats.blocks
    for c in chunks[5:]:
        det.push(c)
    jax.block_until_ready(st.fstate.index.cursor)
    assert st.stats.blocks > blocks_before
    n1 = len(jax.live_arrays())
    b1 = sum(a.nbytes for a in jax.live_arrays())
    assert (n1, b1) == (n0, b0), (n1 - n0, b1 - b0)


def test_fused_state_does_not_alias_caller_stats():
    """Donating the fused state must not delete the caller's med/mad
    arrays (the state copies them at freeze)."""
    cfg, wf, _, med_mad = _parity_setup()
    mm = (jnp.asarray(med_mad[0]), jnp.asarray(med_mad[1]))
    det = StreamingDetector(cfg, stream_smoke_config(), n_stations=1,
                            med_mad=mm)
    for c in np.array_split(wf, 6):
        det.push(c)
    # the originals survive the donated dispatches…
    assert np.isfinite(np.asarray(mm[0])).all()
    # …and the station still exposes usable statistics
    med, mad = det.stations[0].med_mad
    np.testing.assert_array_equal(np.asarray(med), med_mad[0])


# ---------------------------------------------------------------------------
# data-quality path (ISSUE 4): clean bit-parity + one-dispatch invariants
# ---------------------------------------------------------------------------


def test_quality_path_clean_bit_parity():
    """Acceptance criterion: with every quality feature enabled (reorder
    horizon, saturation quarantine, sample-exact duplicate guard) but no
    pathologies present, the emitted pair set is identical to the
    pre-quality fused path — for given and for self-computed statistics —
    and every quality counter stays zero."""
    from repro.configs.fast_seismic import stream_dirty_smoke_config
    cfg, wf, _, med_mad = _parity_setup()
    for mm in (med_mad, None):
        got, quality = {}, None
        for name, scfg in (("base", stream_smoke_config()),
                           ("quality", stream_dirty_smoke_config())):
            det = StreamingDetector(cfg, scfg, n_stations=1, med_mad=mm)
            for c in np.array_split(wf, 10):
                det.push(c)
            got[name], fstats = _pair_set(det)
            quality = fstats["quality"]
        assert got["base"] == got["quality"], (
            mm is None, sorted(got["base"] ^ got["quality"]))
        assert len(got["base"]) > 0
        assert all(v == 0 for v in quality.values()), quality


def test_quality_path_single_dispatch_invariants():
    """Acceptance criterion: the one-dispatch invariants survive the
    quality path — ≤1 steady-state trace and zero retained bytes/chunk,
    including across a gap-masked block mid-steady-state (masks route
    through the already-traced ``step_block``, never re-splitting or
    retracing the hot path)."""
    from repro.configs.fast_seismic import stream_dirty_smoke_config
    cfg, wf, _, med_mad = _parity_setup()
    scfg = stream_dirty_smoke_config()
    wf = wf.copy()
    mid = wf.size * 3 // 4
    wf[mid: mid + 900] = np.nan           # a gap inside the steady state
    det = StreamingDetector(cfg, scfg, n_stations=1, med_mad=med_mad)
    st = det.stations[0]
    chunks = np.array_split(wf, 10)
    adv_start = FU.step_advance._cache_size()
    for c in chunks[:5]:
        det.push(c)
    assert st.stats.blocks >= 2
    jax.block_until_ready(st.fstate.index.cursor)
    adv_before = FU.step_advance._cache_size()
    blk_before = FU.step_block._cache_size()
    # ≤1 new steady-state trace (0 when another quality test already
    # traced these statics in-process)
    assert adv_before - adv_start <= 1
    n0 = len(jax.live_arrays())
    b0 = sum(a.nbytes for a in jax.live_arrays())
    blocks_before = st.stats.blocks
    for c in chunks[5:]:
        det.push(c)
    jax.block_until_ready(st.fstate.index.cursor)
    assert st.stats.blocks > blocks_before
    assert st.qc["suppressed_fingerprints"] > 0   # the gap really was masked
    assert FU.step_advance._cache_size() == adv_before
    assert FU.step_block._cache_size() == blk_before
    n1 = len(jax.live_arrays())
    b1 = sum(a.nbytes for a in jax.live_arrays())
    assert (n1, b1) == (n0, b0), (n1 - n0, b1 - b0)


# ---------------------------------------------------------------------------
# cross-window merge pass (bounded-mode boundary artifact)
# ---------------------------------------------------------------------------


def _merge_cfg():
    fp = F.FingerprintConfig(img_freq=16, img_time=32, img_hop=8, top_k=64,
                             mad_sample_rate=1.0)
    return DetectConfig(
        fingerprint=fp,
        lsh=LSHConfig(n_tables=20, n_funcs=4, n_matches=2, bucket_cap=4,
                      min_dt=fp.overlap_fingerprints, occurrence_frac=0.0),
        align=AlignConfig(min_cluster_size=1, min_cluster_sim=4))


def test_cross_window_merge_boundary_cluster():
    """A diagonal cluster straddling a rolling-filter window boundary is
    split by the per-window clustering and re-merged by the cross-window
    pass before association (regression for the ROADMAP artifact)."""
    cfg = _merge_cfg()
    filt = RollingPairFilter(cfg, window=64, lookback=128)
    # one repeating source: pairs on diagonal dt=40 whose later members
    # span the first window close at id 64
    idx2 = np.arange(58, 71)
    tri = np.stack([idx2 - 40, idx2, np.full_like(idx2, 8)], axis=1)
    filt.add(tri)
    filt.advance(200)           # closes [0,64), [64,128), [128,192)
    assert filt.windows_closed >= 2
    raw = np.concatenate(filt.event_rows, axis=0)
    assert raw.shape[0] == 2    # the boundary split happened…
    merged = filt.all_rows()
    assert merged.shape[0] == 1  # …and the merge pass undoes it
    dt, onset, extent, size, score = merged[0]
    assert dt == 40 and onset == 18 and extent == 12
    assert size == raw[:, 3].sum() and score == raw[:, 4].sum()
    # rows_tail (the incremental association feed) sees the merged row too
    assert filt.rows_tail(0).shape[0] == 1


def test_merge_boundary_rows_keeps_distinct_clusters():
    """Rows on far diagonals or with disjoint idx ranges never merge."""
    acfg = AlignConfig()
    rows = np.array([
        [40, 18, 5, 6, 48],     # base cluster
        [40, 60, 4, 5, 40],     # same diagonal, far beyond gap → distinct
        [90, 18, 5, 6, 48],     # different diagonal → distinct
        [41, 24, 6, 7, 56],     # adjacent diagonal, touching → merges
    ], np.int64)
    out = merge_boundary_rows(rows, acfg)
    assert out.shape[0] == 3
    merged = out[(out[:, 1] == 18) & (out[:, 0] != 90)]
    assert merged.shape[0] == 1 and merged[0, 3] == 13
    # higher-score member donates the representative dt
    assert merged[0, 0] == 41


def test_merge_boundary_rows_bridge_union():
    """Regression (ISSUE 9): two clusters not pairwise-near are joined by
    a bridging row near both. The old first-match-only pass merged the
    bridge into the first cluster and left the second stranded (2 rows);
    the union pass yields one deterministic component."""
    acfg = AlignConfig()                      # dt_merge_tol=2, gap=10
    rows = np.array([
        [40, 0, 5, 6, 48],      # cluster 1
        [44, 8, 2, 3, 24],      # cluster 2: |44-40| > dt_merge_tol
        [42, 12, 3, 4, 60],     # bridge: within tol + gap of BOTH
    ], np.int64)
    out = merge_boundary_rows(rows, acfg)
    assert out.shape[0] == 1, out
    dt, onset, extent, size, score = out[0]
    assert dt == 42             # highest-score member's original dt
    assert onset == 0 and extent == 15
    assert size == 13 and score == 132
    # deterministic under any input ordering
    for perm in ([1, 0, 2], [2, 1, 0], [1, 2, 0]):
        assert np.array_equal(merge_boundary_rows(rows[perm], acfg), out)


def test_merge_boundary_rows_three_window_chain():
    """A single diagonal straddling THREE rolling-filter windows surfaces
    as three boundary rows and re-merges into one span."""
    cfg = _merge_cfg()
    filt = RollingPairFilter(cfg, window=64, lookback=128)
    idx2 = np.arange(58, 136)   # later members span closes at 64 and 128
    tri = np.stack([idx2 - 40, idx2, np.full_like(idx2, 8)], axis=1)
    filt.add(tri)
    filt.advance(260)           # closes [0,64), [64,128), [128,192)
    assert filt.windows_closed >= 3
    raw = np.concatenate(filt.event_rows, axis=0)
    assert raw.shape[0] == 3    # split at both boundaries…
    merged = filt.all_rows()
    assert merged.shape[0] == 1  # …and the chain re-joins end to end
    dt, onset, extent, size, score = merged[0]
    assert dt == 40 and onset == 18 and onset + extent == 95
    assert size == raw[:, 3].sum() and score == raw[:, 4].sum()


# ---------------------------------------------------------------------------
# engine composition + serving
# ---------------------------------------------------------------------------


def test_poll_reemits_on_station_multiplicity_upgrade():
    """Regression (ISSUE 9): a group first alerted at 2 stations re-emits
    (flagged as an upgrade) when a third station's events arrive in a
    later window — the old (dt, onset)-only dedup suppressed it forever."""
    from repro.stream.engine import ALERT_COLS
    cfg, scfg = smoke_config(), stream_bounded_smoke_config()
    det = StreamingDetector(cfg, scfg, n_stations=3)

    def close_with(station, row):
        det.stations[station].filter.event_rows.append(
            np.asarray([row], np.int64))
        det.stations[station].filter.windows_closed += 1

    # two stations see the repeating pair first
    close_with(0, (50, 100, 4, 3, 24))
    close_with(1, (50, 103, 4, 3, 21))
    first = det.poll_detections()
    assert first.shape == (1, ALERT_COLS)
    assert first[0, 2] == 2 and first[0, 4] == 0       # fresh, 2 stations
    # a re-poll with no new window closes is silent
    assert det.poll_detections().shape[0] == 0
    # the third station reports in a later window → upgrade re-emission
    close_with(2, (51, 105, 4, 3, 18))
    second = det.poll_detections()
    assert second.shape == (1, ALERT_COLS), second
    assert second[0, 2] == 3 and second[0, 4] == 1     # upgraded to 3
    # same multiplicity again → deduped as before
    close_with(0, (50, 101, 4, 2, 16))
    assert det.poll_detections().shape[0] == 0


def test_streaming_located_alerts_end_to_end():
    """The streaming locate tier: physical-geometry scenario in, alerts
    carry milli-km locations + milli-magnitudes, the finalize detections
    carry the located columns, and the telemetry locate view counts the
    stack passes."""
    from repro.configs.fast_seismic import located_smoke_config
    from repro.core import locate as LO
    from repro.stream.engine import ALERT_COLS
    cfg, scfg = located_smoke_config(), stream_bounded_smoke_config()
    ds = make_dataset(SynthConfig(duration_s=900.0, n_stations=4,
                                  n_sources=2, events_per_source=6,
                                  event_snr=3.0, seed=11,
                                  physical_geometry=True))
    det = StreamingDetector(cfg, scfg, n_stations=4,
                            station_xy=ds.station_xy)
    assert det.locating
    for start in range(0, ds.waveforms.shape[1], 6000):
        det.push(ds.waveforms[:, start: start + 6000])
    alerts = np.concatenate(det.alerts, axis=0)
    assert alerts.shape[0] >= 1 and alerts.shape[1] == ALERT_COLS
    located = alerts[alerts[:, 5] != LO.LOC_NONE]
    assert located.shape[0] >= 1       # at least one alert localized
    assert (located[:, 5] >= 0).all() and (located[:, 5] <= 50_000).all()
    assert (located[:, 7] != LO.MAG_NONE).any()   # …and sized
    detections, _, stats = det.finalize()
    assert "moveout_rejected" in stats
    v = np.asarray(detections["valid"])
    assert int(v.sum()) == stats["detections"] >= 1
    assert np.isfinite(np.asarray(detections["x_km"])[v]).all()
    assert (np.asarray(detections["station_weight"]) > 0).all()
    view = det.telemetry.locate_view()
    assert view["passes"] >= 2 and view["located"] >= 1
    assert view["stack_wall"]["count"] == view["passes"]
    snap = det.metrics_snapshot()
    assert snap["locate"]["passes"] == view["passes"]


def test_multi_station_streaming_detections():
    cfg, scfg = smoke_config(), stream_smoke_config()
    ds = make_dataset(SynthConfig(duration_s=600.0, n_stations=3,
                                  n_sources=2, events_per_source=5,
                                  event_snr=3.0, seed=11))
    det = StreamingDetector(cfg, scfg, n_stations=3)
    for start in range(0, ds.waveforms.shape[1], 6000):
        det.push(ds.waveforms[:, start: start + 6000])
    detections, events, stats = det.finalize()
    assert detections is not None
    assert stats["detections"] >= 1          # reoccurring sources found
    assert len(stats["ingest"]) == 3
    assert all(s["fingerprints"] > 0 for s in stats["ingest"])


def test_serve_detect_end_to_end():
    """The slot/refill loop now answers against the per-station index
    pool (default 2 stations)."""
    from repro.launch import serve_detect
    stats = serve_detect.main(["--requests", "6", "--slots", "3",
                               "--duration-s", "400"])
    assert stats["requests"] == 6
    assert stats["stations"] == 2
    assert stats["hit_requests"] >= 1        # event windows match corpus


@pytest.mark.slow
def test_bench_e2e_smoke(tmp_path, monkeypatch):
    """``make bench-smoke`` contract: the quick e2e benchmark runs, emits
    a schema-stable BENCH_e2e.json, and the fused path does not regress
    below the unfused chain (perf regressions are one command to spot)."""
    import sys
    root = str(pathlib.Path(__file__).parent.parent)
    if root not in sys.path:
        sys.path.insert(0, root)
    monkeypatch.setenv("BENCH_OUT_DIR", str(tmp_path))
    from benchmarks import bench_e2e
    out = bench_e2e.main(["--quick"])
    assert out["schema"] == "bench-e2e/v4"
    assert set(out) >= {"config_hash", "backend", "step", "points",
                        "offline_replay", "emission", "sharded_pool",
                        "ratios", "metrics"}
    assert out["metrics"]["schema"] == "stream-metrics/v1"
    assert out["metrics"]["stations"] == 4
    written = json.loads((tmp_path / "BENCH_e2e.json").read_text())
    assert written["config_hash"] == out["config_hash"]
    stations = sorted(p["stations"] for p in out["points"] if p["fused"])
    assert stations == [1, 4, 8]
    # the headline claim, with slack for shared-machine timing noise
    assert out["ratios"]["fused_speedup_vs_unfused_chain"] >= 1.2
    # donation: the fused steady state retains no device memory per chunk
    # (the unfused reference may release warmup buffers → delta ≤ 0)
    assert all(p["live_bytes_delta_per_chunk"] == 0
               for p in out["points"] if p["fused"])
    assert all(p["live_bytes_delta_per_chunk"] <= 0 for p in out["points"])
    # offline replay (ISSUE 5): unified batch driver at 1/4/8 stations,
    # at least as fast as the legacy host loop at 4 stations
    replay = out["offline_replay"]
    assert sorted(p["stations"] for p in replay["points"]) == [1, 4, 8]
    assert replay["speedup_vs_legacy_4st"] >= 1.0
    assert out["ratios"]["offline_replay_speedup_vs_legacy_4st"] \
        == replay["speedup_vs_legacy_4st"]
    # v3: the repeat-seeded stream exercises real emission (the v2 points
    # all recorded pairs: 0) and every point carries the wall split
    assert all(p["pairs"] > 0 for p in out["emission"]["points"])
    for p in out["points"]:
        assert p["pairs"] > 0
        assert {"device_step_ms_p50", "host_tail_ms_p50",
                "pair_bytes_per_block"} <= set(p)
        # v4: the primary percentiles are exact wall quantiles; the
        # log-bucketed histogram values moved to *_hist keys
        assert {"device_step_ms_p50_hist",
                "host_tail_ms_p50_hist"} <= set(p)
    # v4: the sharded-pool device grid ran with exact step percentiles
    # and bit-identical pair counts between the sharded and vmap pools
    sp = out["sharded_pool"]
    assert sp["points"] and all(p["pair_parity"] for p in sp["points"])
    assert any(p["devices"] == 8 and p["stations"] == 8
               for p in sp["points"])
    assert out["ratios"]["sharded_pool_speedup_8st_8dev"] \
        == sp["speedup_8st_8dev"]
    # emission A/B (ISSUE 8): dense vs compact at 1/4/8 stations, the
    # compacted pipe is the configured ≥10x smaller, and compaction
    # drops nothing on the clean seeded stream (identical pair counts)
    em = {(p["stations"], p["variant"]): p
          for p in out["emission"]["points"]}
    assert sorted(em) == [(s, v) for s in (1, 4, 8)
                          for v in ("compact", "dense")]
    assert out["emission"]["pair_byte_reduction_t100"] >= 10.0
    assert out["ratios"]["emission_pair_byte_reduction_t100"] \
        == out["emission"]["pair_byte_reduction_t100"]
    for s in (1, 4, 8):
        assert em[(s, "compact")]["pairs"] == em[(s, "dense")]["pairs"]
        assert em[(s, "compact")]["overflow_pairs"] == 0
