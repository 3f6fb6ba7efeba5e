"""Mesh-sharded station pool (ISSUE 10): single-device fallback parity,
elastic add/remove, 8-forced-device bit-parity with donation/retrace
guards, mesh-elastic snapshot round-trip (save@8 → restore@1/4), and the
bench-e2e/v4 sharded-grid schema guard."""
import dataclasses
import hashlib
import json
import pathlib

import numpy as np
import pytest

from conftest import run_forced_devices
from repro.configs.fast_seismic import (latency_config, smoke_config,
                                        stream_bounded_smoke_config,
                                        stream_latency_smoke_config)
from repro.core.synth import SynthConfig, make_dataset
from repro.stream import StreamingDetector


def _stream(cfg, scfg, wf, n_stations, chunk=6000):
    det = StreamingDetector(cfg, scfg, n_stations=n_stations)
    for start in range(0, wf.shape[1], chunk):
        det.push(wf[:n_stations, start:start + chunk])
    return det


def test_sharded_falls_back_without_mesh():
    """On a single visible device ``sharded=True`` is inert: the mesh
    probe returns None, the pool pads nothing, and the stream is
    bit-identical to an explicit ``sharded=False`` run (the
    ``pool_step_*_sharded`` entries delegate to the vmap pool)."""
    cfg, scfg = smoke_config(), stream_bounded_smoke_config()
    ds = make_dataset(SynthConfig(duration_s=600.0, n_stations=3,
                                  n_sources=2, events_per_source=5,
                                  event_snr=3.0, seed=11))
    assert scfg.sharded                      # on by default
    det_s = _stream(cfg, scfg, ds.waveforms, 3)
    assert det_s.mesh is None and det_s.pool_pad == 0
    det_v = _stream(cfg, dataclasses.replace(scfg, sharded=False),
                    ds.waveforms, 3)
    out_s, out_v = det_s.finalize(), det_v.finalize()
    for a, b in zip(out_s[0], out_v[0]):     # detections, bit-identical
        assert np.array_equal(a, b)
    assert [int(st.stats.pairs) for st in det_s.stations] \
        == [int(st.stats.pairs) for st in det_v.stations]


def test_elastic_add_remove_station():
    """``add_station`` grows the live pool at the network frontier and
    ``remove_station`` shrinks it back; both re-pack the stacked pytree
    and the stream keeps running across the width changes."""
    cfg, scfg = latency_config(), stream_latency_smoke_config()
    rng = np.random.default_rng(3)
    chunk = scfg.block_fingerprints * cfg.fingerprint.lag_samples
    det = StreamingDetector(cfg, scfg, n_stations=2)
    with pytest.raises(ValueError, match="live pool"):
        det.add_station()                     # stats not frozen yet
    for c in range(scfg.stats_warmup_blocks + 4):
        det.push(rng.standard_normal((2, chunk)).astype(np.float32))
    assert det.pstate is not None
    i = det.add_station()
    assert i == 2 and len(det.stations) == 3
    # the joiner mirrors a peer's framing position with an all-missing
    # pre-join span, so lockstep block emission holds immediately
    assert det.stations[2].ring.start == det.stations[0].ring.start
    assert det.stations[2].ring.quality["missing_samples"] > 0
    for c in range(4):
        det.push(rng.standard_normal((3, chunk)).astype(np.float32))
    assert all(st.stats.chunks > 0 for st in det.stations)
    det.remove_station(1)
    assert len(det.stations) == 2
    assert [st._pool_idx for st in det.stations] == [0, 1]
    for c in range(2):
        det.push(rng.standard_normal((2, chunk)).astype(np.float32))
    with pytest.raises(ValueError, match="last station"):
        det.remove_station(0), det.remove_station(0)


@pytest.mark.slow
def test_sharded_pool_bit_parity_8_devices():
    """Property test on 8 forced host devices: the mesh-sharded pool ==
    the vmap pool == the sequential solo stations, bit for bit, and the
    sharded entries hold the donation + ≤1-steady-state-trace
    invariants."""
    run_forced_devices("""
import dataclasses, numpy as np, jax
from repro.configs.fast_seismic import smoke_config, \\
    stream_bounded_smoke_config
from repro.core.synth import SynthConfig, make_dataset
from repro.stream import StreamingDetector
from repro.stream import fused as FU

assert jax.device_count() == 8
cfg, scfg = smoke_config(), stream_bounded_smoke_config()
ds = make_dataset(SynthConfig(duration_s=600.0, n_stations=3,
                              n_sources=2, events_per_source=5,
                              event_snr=3.0, seed=11))
wf = ds.waveforms
chunks = [wf[:, s:s + 6000] for s in range(0, wf.shape[1], 6000)]

det = StreamingDetector(cfg, scfg, n_stations=3)
for c in chunks[:6]:
    det.push(c)
assert det.mesh is not None and det.mesh.devices.size == 3
assert det.pool_pad == 0
# donation: steady-state chunks retain zero device bytes
live0 = sum(a.nbytes for a in jax.live_arrays())
for c in chunks[6:8]:
    det.push(c)
assert sum(a.nbytes for a in jax.live_arrays()) == live0
# retracing: one block entry + one advance entry, one trace each
assert len(FU._SHARDED_ENTRIES) <= 2
assert all(fn._cache_size() == 1 for fn in FU._SHARDED_ENTRIES.values())
for c in chunks[8:]:
    det.push(c)
assert all(fn._cache_size() == 1 for fn in FU._SHARDED_ENTRIES.values())

det_v = StreamingDetector(cfg, dataclasses.replace(scfg, sharded=False),
                          n_stations=3)
seq = StreamingDetector(cfg, dataclasses.replace(
    scfg, pooled=False, sharded=False), n_stations=3)
for c in chunks:
    det_v.push(c)
    seq.push(c)
out, out_v, out_seq = det.finalize(), det_v.finalize(), seq.finalize()
for a, b, c in zip(out[0], out_v[0], out_seq[0]):
    assert np.array_equal(a, b) and np.array_equal(a, c)
pairs = [int(st.stats.pairs) for st in det.stations]
assert pairs == [int(st.stats.pairs) for st in det_v.stations]
assert pairs == [int(st.stats.pairs) for st in seq.stations]
print("PARITY", pairs)
""")


@pytest.mark.slow
def test_mesh_elastic_snapshot_roundtrip(tmp_path):
    """A pool snapshotted under an 8-device mesh restores onto 1 and 4
    devices and finishes the stream bit-identically: snapshots are
    per-station slices, so device topology never reaches disk."""
    common = """
import hashlib, numpy as np, jax
from repro.configs.fast_seismic import smoke_config, \\
    stream_bounded_smoke_config
from repro.core.synth import SynthConfig, make_dataset
from repro.stream import StreamingDetector

cfg, scfg = smoke_config(), stream_bounded_smoke_config()
ds = make_dataset(SynthConfig(duration_s=600.0, n_stations=8,
                              n_sources=2, events_per_source=5,
                              event_snr=3.0, seed=11))
wf = ds.waveforms
starts = list(range(0, wf.shape[1], 6000))
half = len(starts) // 2

def digest(det):
    h = hashlib.sha256()
    dets, events, stats = det.finalize()
    for a in dets:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest(), [int(st.stats.pairs) for st in det.stations]
"""
    save = run_forced_devices(common + f"""
det = StreamingDetector(cfg, scfg, n_stations=8)
for s in starts[:half]:
    det.push(wf[:, s:s + 6000])
assert det.mesh is not None and det.mesh.devices.size == 8
det.snapshot({str(tmp_path)!r})
for s in starts[half:]:
    det.push(wf[:, s:s + 6000])
print("DIGEST", *digest(det))
""", devices=8)
    ref = save.splitlines()[-1]
    for devices, width in ((1, None), (4, 4)):
        out = run_forced_devices(common + f"""
det, step = StreamingDetector.restore({str(tmp_path)!r}, cfg, scfg)
assert (det.mesh.devices.size if det.mesh else None) == {width!r}
for s in starts[half:]:
    det.push(wf[:, s:s + 6000])
print("DIGEST", *digest(det))
""", devices=devices)
        assert out.splitlines()[-1] == ref, (devices, out, ref)


def test_bench_sharded_grid_refuses_accelerator(monkeypatch):
    """The sharded grid times forced CPU host devices in child
    interpreters; under an accelerator it raises instead of quietly
    measuring the host."""
    import sys

    import jax
    root = str(pathlib.Path(__file__).parent.parent)
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks import bench_e2e
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="cannot measure a tpu"):
        bench_e2e.sharded_pool_points(quick=True)


@pytest.mark.slow
def test_bench_sharded_grid_schema(tmp_path, monkeypatch):
    """``make bench-sharded`` contract: the quick grid runs its forced-
    device children, every point carries exact (non-histogram) step
    percentiles and passes pair parity, and the flagship 8st × 8dev
    ratio lands in the ratios block."""
    import sys
    root = str(pathlib.Path(__file__).parent.parent)
    if root not in sys.path:
        sys.path.insert(0, root)
    monkeypatch.setenv("BENCH_OUT_DIR", str(tmp_path))
    from benchmarks import bench_e2e
    out = bench_e2e.main(["--sharded", "--quick"])
    assert out["schema"] == "bench-e2e/v4"
    sp = out["sharded_pool"]
    assert sp["host_cores"] >= 1
    assert {(p["devices"], p["stations"]) for p in sp["points"]} \
        == {(2, 4), (8, 8)}
    for p in sp["points"]:
        assert p["pair_parity"]
        assert p["sharded"]["mesh_devices"] == min(p["devices"],
                                                   p["stations"])
        assert p["baseline"]["mesh_devices"] == 1
        for v in ("sharded", "baseline"):
            assert p[v]["device_step_ms_p50"] > 0
            assert p[v]["device_step_ms_p95"] >= p[v]["device_step_ms_p50"]
    assert out["ratios"]["sharded_pool_speedup_8st_8dev"] \
        == sp["speedup_8st_8dev"] > 0
    # parallel scaling needs physical cores: with ≥8 the flagship point
    # must beat the single-device vmap baseline; time-sliced forced
    # devices on fewer cores can only measure the sharding overhead
    if sp["host_cores"] >= 8:
        assert sp["speedup_8st_8dev"] > 1.0
    written = json.loads((tmp_path / "BENCH_e2e.json").read_text())
    assert written["sharded_pool"]["speedup_8st_8dev"] \
        == sp["speedup_8st_8dev"]
