"""Three-component stations on the streaming path (arXiv:1803.09835 §7.1,
channel level), held against the plain reference at a CPU size:

* the host twins the rolling filter's close and the per-push poll run
  (``lsh.occurrence_keep``, ``align.merge_channels_host``,
  ``align.cluster_station_host``, ``align.associate_network_host``) equal
  their jitted counterparts on seeded triplets and event rows;
* 3 stations x 3 components through ``StreamingDetector(..., channels=3)``
  with filter windows that close several times: each close's merged
  triplets and each station's event rows equal ``lsh.search`` per
  component, the §6.5 filter per component and window, a plain numpy
  channel sum and threshold, then ``cluster_station``;
* ``detect_events`` on (S, C, T) gives the streaming ``finalize``'s
  events and detections;
* one component (C = 1) gives what the jitted close gave, and an
  (S, 1, T) push is an (S, T) push;
* a C = 3 snapshot restores and resumes with identical output; stations
  join and leave with their components; serving refuses C > 1.
"""
import dataclasses

import numpy as np
import pytest

from repro.configs.fast_seismic import smoke_config
from repro.core import align as A
from repro.core import lsh as L
from repro.core.align import AlignConfig
from repro.core.detect import detect_events, station_stats
from repro.core.lsh import Pairs
from repro.core.synth import SynthConfig, make_dataset
from repro.stream import engine as E
from repro.stream.engine import StreamingDetector, events_to_rows
from repro.stream.index import StreamIndexConfig
from repro.stream.ingest import StreamConfig
from repro.utils import unpack_bits

S, C = 3, 3
FILTER_WINDOW = 96


def _waves(seed=5, duration_s=800.0):
    """(S, C, T): each station's components share its events' onsets and
    carry noise of their own."""
    ds = make_dataset(SynthConfig(duration_s=duration_s, n_stations=S,
                                  n_sources=2, events_per_source=5,
                                  event_snr=3.0, seed=seed))
    rng = np.random.default_rng(seed)
    base = np.asarray(ds.waveforms, np.float32)
    noise = rng.standard_normal((S, C, base.shape[1])).astype(np.float32)
    return base[:, None, :] + np.float32(0.5) * noise


def _scfg(filter_window=FILTER_WINDOW):
    """No expiry within the trace, no limiter or quarantine, every
    fingerprint kept in the verify ring: the pairs are the offline
    search's, so the reference can be ``lsh.search``."""
    return StreamConfig(block_fingerprints=64,
                        index=StreamIndexConfig(n_buckets=2048, bucket_cap=8,
                                                pk_slots=4096),
                        stats_warmup_blocks=2, reservoir_rows=1024,
                        max_pairs_per_block=1024, verify_jaccard=True,
                        window_fingerprints=4096 if filter_window else 0,
                        filter_window_fingerprints=filter_window)


def _stats(streams, fcfg):
    meds, mads = station_stats(streams, fcfg)
    return np.asarray(meds), np.asarray(mads)


def _push_all(det, waves, n_chunks=7):
    for chunk in np.array_split(waves, n_chunks, axis=-1):
        det.push(chunk)


def _triplets(pairs: Pairs) -> np.ndarray:
    v = np.asarray(pairs.valid)
    return np.stack([np.asarray(pairs.idx1)[v], np.asarray(pairs.idx2)[v],
                     np.asarray(pairs.sim)[v]], axis=1).astype(np.int64)


# ---------------------------------------------------------------------------
# host twins against the jitted stages
# ---------------------------------------------------------------------------


def _random_triplets(rng, n, span=300):
    i1 = rng.integers(0, span, n)
    i2 = i1 + rng.integers(4, 40, n)
    return np.stack([i1, i2, rng.integers(1, 6, n)], axis=1)


def _twin_occurrence(rng):
    tri = _random_triplets(rng, 700)
    tri = tri[np.unique(tri[:, 0] * 1000 + tri[:, 1], return_index=True)[1]]
    n_fp = int(tri[:, 1].max()) + 1
    pairs = E.pairs_from_triplets(tri)
    for limit in (None, 6):
        filt, _ = L.occurrence_filter(pairs, n_fp, 0.02, limit=limit)
        keep = L.occurrence_keep(tri[:, 0], tri[:, 1], n_fp, 0.02,
                                 limit=limit)
        assert 0 < keep.sum() < keep.size
        np.testing.assert_array_equal(np.asarray(filt.valid)[:len(tri)],
                                      keep)


def _twin_merge_cluster(rng):
    acfg = AlignConfig(min_cluster_size=2, min_cluster_sim=6)
    chans = [_random_triplets(rng, n, span=120) for n in (400, 250, 0)]
    merged = A.merge_channels(
        [(p.dt, p.idx1, p.sim, p.valid)
         for p in map(E.pairs_from_triplets, chans)], 4)
    host = A.merge_channels_host(chans, 4)
    np.testing.assert_array_equal(_triplets(merged), host)
    rows = events_to_rows(A.cluster_station(merged, acfg))
    assert rows.shape[0] > 2
    np.testing.assert_array_equal(A.cluster_station_host(host, acfg), rows)


def _twin_associate(rng):
    acfg = AlignConfig(min_stations=2, max_group_extent=40)
    rows = []
    for st in range(4):
        k = int(rng.integers(5, 40))
        r = np.zeros((k, 5), np.int64)
        r[:, 0] = rng.integers(20, 60, k)
        r[:, 1] = rng.integers(0, 400, k)
        r[:, 2:] = rng.integers(1, 9, (k, 3))
        rows.append(r)
    det = A.associate_network([E.events_from_rows(r) for r in rows], acfg,
                              4, with_onsets=True)
    host = A.associate_network_host(rows, acfg, with_onsets=True)
    v = np.asarray(det["valid"])
    assert v.sum() > 0
    for name in ("dt", "onset", "onset_span", "n_stations", "score",
                 "station_onset", "station_score"):
        np.testing.assert_array_equal(np.asarray(det[name])[v], host[name],
                                      err_msg=name)


@pytest.mark.parametrize("check", [_twin_occurrence, _twin_merge_cluster,
                                   _twin_associate],
                         ids=["occurrence", "merge_cluster", "associate"])
def test_host_twins_equal_the_jitted_stages(check):
    check(np.random.default_rng(17))


# ---------------------------------------------------------------------------
# the streaming detector against the plain reference
# ---------------------------------------------------------------------------


def _reference_station(det, streams, n_fp, cfg, scfg):
    """Per close of each station: (merged triplets, rows) from
    ``lsh.search`` per component, the §6.5 filter per component over the
    window's rebased span, a numpy channel sum and threshold, and
    ``cluster_station``; returns {station: [(window, merged, rows)]}."""
    fcfg, lcfg, acfg = cfg.fingerprint, cfg.lsh, cfg.align
    raw_cfg = dataclasses.replace(lcfg, bucket_cap=scfg.index.bucket_cap,
                                  occurrence_frac=0.0)
    fw, back = scfg.filter_window_fingerprints, scfg.window_fingerprints
    out = {}
    for s in range(S):
        comps = []
        for c in range(C):
            bits = unpack_bits(det.pstate.index.pk[s * C + c, :n_fp],
                               fcfg.fp_dim)
            comps.append(_triplets(L.search(bits, raw_cfg)[0]))
        closes = []
        for w in range(-(-n_fp // fw)):
            cur = [t[t[:, 1] // fw == w] for t in comps]
            if not any(t.shape[0] for t in cur):
                continue
            base = w * fw - back
            kept = []
            for t in cur:
                local = E.pairs_from_triplets(
                    np.stack([t[:, 0] - base, t[:, 1] - base, t[:, 2]], 1))
                filt, _ = L.occurrence_filter(
                    local, back + fw, lcfg.occurrence_frac,
                    limit=max(1, int(lcfg.occurrence_frac * fw)))
                kept.append(t[np.asarray(filt.valid)[:t.shape[0]]])
            # the channel sum of each (dt, idx1), thresholded
            allk = np.concatenate(kept)
            sums: dict = {}
            for i1, i2, sim in allk.tolist():
                sums[(i2 - i1, i1)] = sums.get((i2 - i1, i1), 0) + sim
            merged = np.array(sorted(
                (i1, i1 + dt, tot) for (dt, i1), tot in sums.items()
                if tot >= acfg.channel_threshold),
                np.int64).reshape(-1, 3)
            merged = merged[np.lexsort((merged[:, 0],
                                        merged[:, 1] - merged[:, 0]))]
            rows = events_to_rows(A.cluster_station(
                E.pairs_from_triplets(merged), acfg))
            closes.append((w, merged, rows))
        out[s] = closes
    return out


def test_three_component_stream_matches_reference(monkeypatch):
    cfg, scfg = smoke_config(), _scfg()
    waves = _waves()
    streams = waves.reshape(S * C, -1)
    seen = []
    real = A.merge_channels_host
    monkeypatch.setattr(A, "merge_channels_host",
                        lambda tris, thr: seen.append(real(tris, thr))
                        or seen[-1])
    det = StreamingDetector(cfg, scfg, n_stations=S, channels=C,
                            med_mad=_stats(streams, cfg.fingerprint))
    assert det.pooled and len(det.stations) == S * C
    assert det.n_stations == S and len(det.filters) == S
    assert det.station_streams(1)[2] is det.stations[5]
    assert all(st.filter is det.filters[i // C]
               for i, st in enumerate(det.stations))
    _push_all(det, waves)
    det.flush()
    n_fp = det.stations[0].ring.next_fp
    ref = _reference_station(det, streams, n_fp, cfg, scfg)
    detections, events, stats = det.finalize()

    closes = [(s, w) for s in range(S) for w, _, _ in ref[s]]
    assert len(seen) == len(closes)
    assert min(len(ref[s]) for s in range(S)) >= 2
    # each close's merged triplets, station-major in close order
    order = sorted(range(len(closes)), key=lambda i: (closes[i][1],
                                                      closes[i][0]))
    flat = [m for s in range(S) for _, m, _ in ref[s]]
    for got, i in zip(seen, order):
        np.testing.assert_array_equal(got, flat[i])
    merged_any = sum(m.shape[0] for m in flat)
    kept_any = sum(r.shape[0] for s in range(S) for _, _, r in ref[s])
    assert merged_any > 0 and kept_any > 0
    for s in range(S):
        rows = np.concatenate([r for _, _, r in ref[s]]
                              + [np.zeros((0, 5), np.int64)])
        want = E.merge_boundary_rows(rows, cfg.align)
        np.testing.assert_array_equal(det.filters[s].all_rows(), want)
        np.testing.assert_array_equal(events_to_rows(events[s]), want)
        assert stats[f"station{s}_windows"] >= n_fp // FILTER_WINDOW
    ref_det = A.associate_network([E.events_from_rows(
        E.merge_boundary_rows(np.concatenate(
            [r for _, _, r in ref[s]] + [np.zeros((0, 5), np.int64)]),
            cfg.align)) for s in range(S)], cfg.align, S)
    for name in ("dt", "onset", "n_stations", "score", "valid"):
        np.testing.assert_array_equal(np.asarray(detections[name]),
                                      np.asarray(ref_det[name]))
    assert stats["detections"] >= 1
    assert int(np.asarray(detections["n_stations"]).max()) <= S
    # the counters saw what went into and came out of every merge
    view = det.metrics_snapshot()["filter"]
    assert view["windows_closed"] == sum(stats[f"station{s}_windows"]
                                         for s in range(S))
    assert view["merged_kept"] == merged_any
    assert len(view["merge_in"]) == C and min(view["merge_in"]) > 0


def test_detect_events_three_components_matches_stream():
    cfg = smoke_config()
    scfg = _scfg(filter_window=0)
    waves = _waves(seed=9, duration_s=500.0)
    streams = waves.reshape(S * C, -1)
    detections, events, _, stats = detect_events(waves, cfg, scfg=scfg,
                                                 keep_pairs=True)
    assert len(events) == S and len(stats["_station_pairs"]) == S * C
    det = StreamingDetector(cfg, scfg, n_stations=S, channels=C,
                            med_mad=_stats(streams, cfg.fingerprint))
    _push_all(det, streams, n_chunks=5)        # (S·C, T) pushes too
    got, got_events, got_stats = det.finalize()
    assert sum(int(e.count()) for e in events) > 0
    for s in range(S):
        np.testing.assert_array_equal(events_to_rows(got_events[s]),
                                      events_to_rows(events[s]))
        assert got_stats[f"station{s}_pairs"] == stats[f"station{s}_pairs"]
    for name in ("dt", "onset", "n_stations", "score", "valid"):
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(detections[name]))
    # the (S, C, T) replay is the per-component replay, merged per station
    one = detect_events(streams, cfg, scfg=scfg, keep_pairs=True)[3]
    for a, b in zip(stats["_station_pairs"], one["_station_pairs"]):
        np.testing.assert_array_equal(_triplets(a), _triplets(b))


def test_one_component_close_is_the_jitted_close(monkeypatch):
    """C = 1: every close gives the rows the jitted filter, merge and
    cluster gave, and an (S, 1, T) push is an (S, T) push."""
    cfg, scfg = smoke_config(), _scfg(filter_window=64)
    waves = _waves(seed=3)[:, :1]
    lcfg, acfg = cfg.lsh, cfg.align
    calls = []
    real = E.RollingPairFilter._filter_cluster

    def spy(self, tris):
        out = real(self, tris)
        calls.append((self.w_start, [t.copy() for t in tris], out[0]))
        return out

    monkeypatch.setattr(E.RollingPairFilter, "_filter_cluster", spy)
    outs = []
    for shape in (waves.shape, waves.shape[::2]):
        det = StreamingDetector(cfg, scfg, n_stations=S)
        _push_all(det, waves.reshape(shape))
        outs.append((det.finalize(), [f.all_rows() for f in det.filters]))
    assert len(calls) >= 2 * S * 2
    for w_start, (tri,), rows in calls:
        pairs, _ = E.host_occurrence_filter(
            E.pairs_from_triplets(tri), scfg.window_fingerprints
            + scfg.filter_window_fingerprints, lcfg,
            base=w_start - scfg.window_fingerprints,
            limit=max(1, int(lcfg.occurrence_frac
                             * scfg.filter_window_fingerprints)))
        merged = A.merge_channels(
            [(pairs.dt, pairs.idx1, pairs.sim, pairs.valid)],
            acfg.channel_threshold)
        np.testing.assert_array_equal(
            rows, events_to_rows(A.cluster_station(merged, acfg)))
    (d0, _, s0), rows0 = outs[0]
    (d1, _, s1), rows1 = outs[1]
    s0.pop("ingest"), s1.pop("ingest")        # wall times
    assert s0 == s1
    for a, b in zip(rows0, rows1):
        np.testing.assert_array_equal(a, b)
    for name in ("dt", "onset", "n_stations", "score", "valid"):
        np.testing.assert_array_equal(np.asarray(d0[name]),
                                      np.asarray(d1[name]))


def test_three_component_snapshot_restore_resumes(tmp_path):
    cfg, scfg = smoke_config(), _scfg()
    waves = _waves(seed=21)
    med_mad = _stats(waves.reshape(S * C, -1), cfg.fingerprint)
    chunks = np.array_split(waves, 8, axis=-1)
    run = StreamingDetector(cfg, scfg, n_stations=S, channels=C,
                            med_mad=med_mad)
    for c in chunks[:6]:
        run.push(c)
    assert run.filters[0].windows_closed >= 1
    assert any(f.buf_rows for f in run.filters)
    run.snapshot(str(tmp_path), step=6)
    restored, _ = StreamingDetector.restore(str(tmp_path), cfg, scfg)
    assert restored.channels == C and restored.n_stations == S
    for a, b in zip(run.filters, restored.filters):
        assert a.frontier == b.frontier and a.comp_rows == b.comp_rows
    for c in chunks[6:]:
        run.push(c)
        restored.push(c)
    d0, ev0, s0 = run.finalize()
    d1, ev1, s1 = restored.finalize()
    for a, b in zip(ev0, ev1):
        np.testing.assert_array_equal(events_to_rows(a), events_to_rows(b))
    for name in ("dt", "onset", "n_stations", "score", "valid"):
        np.testing.assert_array_equal(np.asarray(d0[name]),
                                      np.asarray(d1[name]))
    assert s0["detections"] == s1["detections"] >= 1
    np.testing.assert_array_equal(np.concatenate(run.alerts),
                                  np.concatenate(restored.alerts))


def test_station_joins_and_leaves_with_its_components():
    cfg, scfg = smoke_config(), _scfg()
    waves = _waves(seed=4, duration_s=300.0)
    det = StreamingDetector(cfg, scfg, n_stations=S, channels=C,
                            med_mad=_stats(waves.reshape(S * C, -1),
                                           cfg.fingerprint))
    det.push(waves[..., :12000])
    new = det.add_station()
    assert new == S and det.n_stations == S + 1
    assert len(det.stations) == (S + 1) * C
    joined = det.station_streams(new)
    assert [st.channel for st in joined] == list(range(C))
    assert all(st.filter is det.filters[new] for st in joined)
    grown = np.concatenate([waves, waves[:1]])[..., 12000:20000]
    det.push(grown)
    det.remove_station(1)
    assert det.n_stations == S and len(det.stations) == S * C
    assert [st._pool_idx for st in det.stations] == list(range(S * C))
    assert det.filters[2] is joined[0].filter
    assert det.stations[3].filter is det.filters[1]
    det.push(waves[..., 20000:])
    with pytest.raises(ValueError, match="streams"):
        det.push(waves[:2, :, :100])


def test_serving_refuses_a_multi_component_detector():
    from repro.launch.serve_detect import ServeDetectEngine
    cfg, scfg = smoke_config(), _scfg(filter_window=0)
    med = np.zeros(cfg.fingerprint.n_coeff, np.float32)
    det = StreamingDetector(cfg, scfg, n_stations=2, channels=C,
                            med_mad=(med, med + 1))
    with pytest.raises(ValueError, match="components"):
        ServeDetectEngine.from_detector(det)
