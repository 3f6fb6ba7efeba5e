"""Bring-up smoke run of the detector on one TPU chip, at the paper's widths.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: sharded pool vs vmap pool

Phases (one process, stops at the first failure):

* device — the default device must be a TPU; nothing runs on the CPU.
* kernels — ``minmax_sig_buckets``, ``jaccard_popcount``, ``stft_mag`` and
  ``haar2d`` compiled for the chip at paper widths on seeded inputs, each
  against its jnp oracle: bit-exact for the integer kernels, a stated
  relative tolerance for the float ones.
* precision — the chip's fingerprint bits against the CPU backend's for the
  same waveform and statistics.
* monitoring — ``StreamingDetector(fast_seismic.config(),
  fast_seismic.stream_config(), n_stations=4)`` fed two hours of seeded
  100 Hz synthetic network data through ``engine.ingest_chunks``, with the
  index state at its full paper size and frozen offline statistics. Per
  station, the streamed pair set must equal the plain reference on the
  same chip (``core/lsh.search`` at the index's bucket window over the
  fingerprints the stream hashed, then the host §6.5 occurrence filter),
  with no compaction overflow.
* backfill — ``core/detect.detect_events`` over the same waveforms with
  ``stream_config()``: its per-station pairs must equal the monitoring
  run's. The same replay with the Pallas kernels switched on
  (``use_pallas`` + ``verify_pallas``) is reported against it.
* serving — a ``ServeDetectEngine`` with ``fast_seismic.serve_config()``'s
  slots, refreshed from the monitoring detector, answers 32 template
  queries centred on known arrivals; at least one must hit.

``--chips 4`` runs only the four-chip phase: 8 stations through the
sharded station pool (a ``stations`` mesh over the four chips) against the
vmap pool on one chip; the pair sets must be identical and each chip must
hold its own shard of the pool state.

Earlier lines are informational (compile seconds, per-block wall time,
peak device bytes). The last line of standard output is one JSON object
naming the device; the exit code is non-zero on any failure.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

DURATION_S = 7200.0       # two hours of 100 Hz data per station
N_STATIONS = 4
N_QUERIES = 32
SEED = 7


def log(msg: str) -> None:
    print(f"# {msg}", flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def device_phase(jax, want: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    log(f"device {json.dumps(info)}")
    if d.platform != "tpu":
        raise SmokeFailure(f"no TPU visible (default device is "
                           f"{d.platform}); this smoke run never falls "
                           f"back to the CPU")
    check(len(devs) == want, f"expected {want} chip(s), JAX sees "
                             f"{len(devs)}")
    return info


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


def timed(jax, fn, *args):
    """(seconds of the first call incl. compile, seconds of a second
    call, result)."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return t1 - t0, time.perf_counter() - t1, out


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

# float kernels: max |kernel - oracle| over max |oracle|. Both sides run
# f32 matmuls at HIGHEST precision; the bound covers accumulation-order
# differences only.
FLOAT_REL_TOL = 1e-4


def kernel_phase(jax, jnp, np, cfg, scfg) -> None:
    from repro.core import lsh as L
    from repro.kernels import ops, ref

    fcfg, lcfg = cfg.fingerprint, cfg.lsh
    rng = np.random.default_rng(SEED)
    n = scfg.block_fingerprints
    d = fcfg.fp_dim

    # Min-Max signatures + buckets: (256, 8192) fingerprints with the
    # paper's ~400 set bits per row, t=100 tables of f=4 functions
    fp = jnp.asarray(rng.random((n, d)) < fcfg.top_k / d)
    mp = L.hash_mappings(d, lcfg)
    salts = L.bucket_salts(lcfg.n_tables, lcfg.seed)
    nb = scfg.index.n_buckets
    kern = jax.jit(lambda f, m, s: ops.minmax_sig_buckets(
        f, m, s, use_minmax=lcfg.use_minmax, n_buckets=nb))
    orac = jax.jit(lambda f, m: (
        L.signatures(f, m, lcfg),
        L.bucket_ids(L.signatures(f, m, lcfg), nb, lcfg.seed)))
    c_k, r_k, (sig_k, bkt_k) = timed(jax, kern, fp, mp, salts)
    c_o, r_o, (sig_o, bkt_o) = timed(jax, orac, fp, mp)
    check(np.array_equal(np.asarray(sig_k), np.asarray(sig_o))
          and np.array_equal(np.asarray(bkt_k), np.asarray(bkt_o)),
          "minmax_sig_buckets differs from the jnp oracle")
    log(f"kernel minmax_sig_buckets {n}x{d} t={lcfg.n_tables}: bit-exact; "
        f"first call {c_k:.3f}s (oracle {c_o:.3f}s), run {r_k * 1e3:.3f}ms "
        f"(oracle {r_o * 1e3:.3f}ms)")

    # exact-Jaccard verify: max_pairs_per_block rows of fp_dim/32 words
    p, w = scfg.max_pairs_per_block, d // 32
    a = jnp.asarray(rng.integers(0, 2**32, (p, w), dtype=np.uint32))
    b = jnp.asarray(rng.integers(0, 2**32, (p, w), dtype=np.uint32))
    c_k, r_k, j_k = timed(jax, jax.jit(ops.jaccard_popcount), a, b)
    c_o, r_o, j_o = timed(jax, jax.jit(ref.jaccard_popcount), a, b)
    check(np.array_equal(np.asarray(j_k), np.asarray(j_o)),
          "jaccard_popcount differs from the jnp oracle")
    log(f"kernel jaccard_popcount {p}x{w}: bit-exact; first call "
        f"{c_k:.3f}s, run {r_k * 1e3:.3f}ms (oracle {r_o * 1e3:.3f}ms)")

    # STFT power: the frames of one 256-fingerprint block, band-cut
    from repro.core import fingerprint as F
    bs = fcfg.block_samples(n)
    x = jnp.asarray(rng.standard_normal(bs).astype(np.float32))
    frames = F.frame(x, fcfg.stft_len, fcfg.stft_hop)
    lo, hi = fcfg.band_bins
    dr, di = ref.dft_matrices(fcfg.stft_len, fcfg.n_rfft)
    win = jnp.asarray(np.hanning(fcfg.stft_len).astype(np.float32))
    dr, di = jnp.asarray(dr[:, lo:hi]), jnp.asarray(di[:, lo:hi])
    c_k, r_k, s_k = timed(jax, jax.jit(ops.stft_mag), frames, win, dr, di)
    c_o, r_o, s_o = timed(jax, jax.jit(ref.stft_mag), frames, win, dr, di)
    err = float(jnp.max(jnp.abs(s_k - s_o)) / jnp.max(jnp.abs(s_o)))
    check(err <= FLOAT_REL_TOL, f"stft_mag rel err {err:.3e} > "
                                f"{FLOAT_REL_TOL:.0e}")
    log(f"kernel stft_mag {tuple(frames.shape)}x{hi - lo}: rel err "
        f"{err:.3e} (tol {FLOAT_REL_TOL:.0e}); first call {c_k:.3f}s, run "
        f"{r_k * 1e3:.3f}ms (oracle {r_o * 1e3:.3f}ms)")

    # 2-D Haar over one block's spectral images
    imgs = jnp.asarray(rng.standard_normal(
        (n, fcfg.img_freq, fcfg.img_time)).astype(np.float32))
    c_k, r_k, h_k = timed(jax, jax.jit(ops.haar2d), imgs)
    c_o, r_o, h_o = timed(jax, jax.jit(ref.haar2d), imgs)
    err = float(jnp.max(jnp.abs(h_k - h_o)) / jnp.max(jnp.abs(h_o)))
    check(err <= FLOAT_REL_TOL, f"haar2d rel err {err:.3e} > "
                                f"{FLOAT_REL_TOL:.0e}")
    log(f"kernel haar2d {tuple(imgs.shape)}: rel err {err:.3e} (tol "
        f"{FLOAT_REL_TOL:.0e}); first call {c_k:.3f}s, run "
        f"{r_k * 1e3:.3f}ms (oracle {r_o * 1e3:.3f}ms)")


# ---------------------------------------------------------------------------
# data, statistics, precision
# ---------------------------------------------------------------------------


def make_network(np, n_stations: int):
    """Seeded synthetic network: ``core/synth`` background noise plus
    repeating sources whose every arrival lands on the fingerprint lag
    grid, so the repeats of one source produce near-identical fingerprints
    and hash-collide. (Sub-lag offsets shift the whole spectral image at
    the paper's widths, and such repeats almost never collide.)"""
    from repro.configs.fast_seismic import config
    from repro.core import synth
    scfg = synth.SynthConfig(duration_s=DURATION_S, n_stations=n_stations,
                             n_sources=4, events_per_source=5,
                             event_snr=8.0, seed=SEED)
    # event_snr=0 keeps make_dataset's own events silent: its waveforms
    # are the noise floor the aligned events below are added to
    wf = synth.make_dataset(
        dataclasses.replace(scfg, event_snr=0.0)).waveforms.copy()
    rng = np.random.default_rng(SEED)
    lag_s = config().fingerprint.lag_samples / scfg.fs
    templates = [synth._source_template(rng, scfg)
                 for _ in range(scfg.n_sources)]
    delays = lag_s * rng.integers(1, 5, (scfg.n_sources, n_stations))
    # one event every slot of 40 lags (80 s), slots dealt to sources
    slots = rng.permutation(int(DURATION_S / lag_s) // 40 - 1)
    n_ev = min(scfg.n_sources * scfg.events_per_source, slots.size)
    times = np.sort(slots[:n_ev]) * 40 * lag_s
    sources = rng.permutation(np.arange(n_ev) % scfg.n_sources)
    amp = scfg.event_snr * scfg.noise_sigma
    for t0, s in zip(times, sources):
        for st in range(n_stations):
            i0 = int(round((t0 + delays[s, st]) * scfg.fs))
            tpl = templates[s]
            wf[st, i0:i0 + tpl.size] += amp * tpl * rng.uniform(0.9, 1.1)
    return synth.SynthDataset(waveforms=wf, event_times=times,
                              event_sources=sources.astype(np.int32),
                              arrival_delays=delays, cfg=scfg)


def precision_phase(jax, jnp, np, cfg, wf, med, mad) -> None:
    """Share of identical fingerprint bits, chip vs the CPU backend, at
    the program's matmul precision and, for the record, at the backend
    default."""
    from repro.core import fingerprint as F
    from repro.kernels import ref
    fcfg = cfg.fingerprint
    x = wf[:fcfg.block_samples(1024)]

    def bits():
        fn = jax.jit(lambda v, m, s: F.fingerprints_from_waveform(
            v, fcfg, med_mad=(m, s))[0])
        return np.asarray(fn(jnp.asarray(x), jnp.asarray(med),
                             jnp.asarray(mad)))

    def compare(label):
        chip = bits()
        with jax.default_device(jax.devices("cpu")[0]):
            cpu = bits()
        log(f"precision {label}: {chip.shape[0]} fingerprints, identical "
            f"bits chip vs cpu {np.mean(chip == cpu):.6f}, identical "
            f"fingerprints {np.mean((chip == cpu).all(axis=1)):.4f}")

    compare(f"{ref.MATMUL_PRECISION}")
    saved = ref.MATMUL_PRECISION
    ref.MATMUL_PRECISION = F.MATMUL_PRECISION = jax.lax.Precision.DEFAULT
    try:
        compare("DEFAULT")
    finally:
        ref.MATMUL_PRECISION = F.MATMUL_PRECISION = saved


# ---------------------------------------------------------------------------
# monitoring, reference, backfill, serving
# ---------------------------------------------------------------------------


def pair_set(np, pairs) -> set:
    v = np.asarray(pairs.valid)
    return set(zip(np.asarray(pairs.idx1)[v].tolist(),
                   np.asarray(pairs.idx2)[v].tolist(),
                   np.asarray(pairs.sim)[v].tolist()))


def monitoring_phase(jax, np, cfg, scfg, ds, med, mad):
    """Stream the network through the detector; returns (detector,
    per-station raw device triplets)."""
    from repro.stream.engine import StreamingDetector, ingest_chunks
    fcfg = cfg.fingerprint
    wf = ds.waveforms
    det = StreamingDetector(cfg, scfg, n_stations=wf.shape[0],
                            med_mad=(med, mad))
    adv = scfg.block_fingerprints * fcfg.lag_samples
    n_chunks = -(-wf.shape[1] // adv)
    walls = det.telemetry.capture_raw_walls()["fused_step"]
    t0 = time.perf_counter()
    ingest_chunks(det, wf, n_chunks=n_chunks)
    det.flush()
    wall = time.perf_counter() - t0
    log(f"monitoring: {wf.shape[0]} stations x {n_chunks} chunks of "
        f"{adv} samples in {wall:.2f}s; {len(walls)} pool steps, the "
        f"first two (block + advance entries, incl. compile) "
        f"{walls[0]:.2f}s {walls[1]:.2f}s, later steps median "
        f"{np.median(walls[2:]) * 1e3:.1f}ms max "
        f"{np.max(walls[2:]) * 1e3:.1f}ms (device step + pull)")
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(det.pstate.index))
    log(f"monitoring: pool index state {state_bytes / 1e6:.1f} MB "
        f"({state_bytes / wf.shape[0] / 1e6:.1f} MB per station)")
    raw = []
    for st in det.stations:
        # the 2 h stream is shorter than the 1-day filter window, so the
        # rolling filter's open window still holds every emitted triplet
        check(st.filter.windows_closed == 0, "a filter window closed")
        raw.append(np.concatenate(st.filter.buf, axis=0) if st.filter.buf
                   else np.zeros((0, 3), np.int64))
    drops = det.telemetry.drop_breakdown()
    log(f"monitoring: pairs emitted {[len(r) for r in raw]}, "
        f"drops {json.dumps(drops)}")
    check(drops["overflow_pairs"] == 0, "compaction overflowed")
    check(sum(len(r) for r in raw) > 0, "no pairs emitted")
    return det, raw


def filtered(np, cfg, tri, n_fp):
    from repro.stream.engine import host_occurrence_filter, \
        pairs_from_triplets
    pairs, _ = host_occurrence_filter(pairs_from_triplets(tri), n_fp,
                                      cfg.lsh)
    return pair_set(np, pairs)


def reference_phase(jax, jnp, np, cfg, scfg, det, wf, med, mad, raw
                    ) -> list:
    """Plain reference per station: the sort-based ``lsh.search`` (with
    its §6.5 occurrence filter) over the fingerprints the stream hashed —
    read back from the detector's bit-packed ring — must equal the
    streamed pairs after the same host filter.

    The search runs at the index's bucket window: a stored bucket holds
    ``scfg.index.bucket_cap`` (8) fingerprints where the offline search
    pairs ranks ``cfg.lsh.bucket_cap`` (4) apart, and on a long run of
    equal signatures the two windows count a different number of tables
    for the same pair (``core/detect.replay_config`` matches them the
    other way round). The whole-trace fingerprints of the same waveform
    are compared with the streamed bits for the record: block-wise and
    whole-trace matmuls may round differently, and top-K binarization
    turns that into flipped bits."""
    from repro.core import fingerprint as F
    from repro.core import lsh as L
    from repro.utils import unpack_bits
    fcfg = cfg.fingerprint
    lcfg = dataclasses.replace(cfg.lsh, bucket_cap=scfg.index.bucket_cap)
    n_fp = fcfg.n_fingerprints(wf.shape[1])
    streamed = []
    t0 = time.perf_counter()
    for st in range(wf.shape[0]):
        bits = unpack_bits(det.pstate.index.pk[st, :n_fp], fcfg.fp_dim)
        whole, _ = F.fingerprints_from_waveform(
            jnp.asarray(wf[st]), fcfg, med_mad=(med[st], mad[st]))
        same = np.asarray(whole) == np.asarray(bits)
        pairs, _ = L.search(bits, lcfg)
        ref = pair_set(np, pairs)
        got = filtered(np, cfg, raw[st], n_fp)
        streamed.append(got)
        log(f"reference station {st}: {len(ref)} pairs, streamed "
            f"{len(got)}, only-reference {sorted(ref - got)[:4]}, "
            f"only-streamed {sorted(got - ref)[:4]}; whole-trace vs "
            f"streamed fingerprints: identical bits {same.mean():.6f}, "
            f"identical fingerprints {same.all(axis=1).mean():.4f}")
        check(got == ref, f"station {st}: streamed pairs differ from "
                          f"lsh.search + occurrence filter")
    log(f"reference: {time.perf_counter() - t0:.2f}s")
    return streamed


def backfill_phase(np, cfg, scfg, ds, streamed) -> None:
    from repro.core.detect import detect_events
    t0 = time.perf_counter()
    _, _, times, stats = detect_events(ds.waveforms, cfg, scfg=scfg,
                                       keep_pairs=True)
    back = [pair_set(np, p) for p in stats["_station_pairs"]]
    log(f"backfill: {time.perf_counter() - t0:.2f}s (fused replay "
        f"{times.fused_step_s:.2f}s), pairs {[len(b) for b in back]}")
    check(back == streamed, "backfill pairs differ from monitoring pairs")

    pcfg = dataclasses.replace(
        cfg, fingerprint=dataclasses.replace(cfg.fingerprint,
                                             use_pallas=True),
        lsh=dataclasses.replace(cfg.lsh, use_pallas=True))
    t0 = time.perf_counter()
    _, _, times, stats = detect_events(
        ds.waveforms, pcfg, keep_pairs=True,
        scfg=dataclasses.replace(scfg, verify_pallas=True))
    pal = [pair_set(np, p) for p in stats["_station_pairs"]]
    diff = [len(a ^ b) for a, b in zip(pal, back)]
    log(f"pallas pool step: {time.perf_counter() - t0:.2f}s (fused replay "
        f"{times.fused_step_s:.2f}s), pairs {[len(p) for p in pal]}; "
        f"agree with kernels off: {pal == back} (symmetric difference "
        f"per station {diff})")


def serving_phase(np, det, cfg, ds) -> None:
    from repro.configs.fast_seismic import serve_config
    from repro.launch.serve_detect import QueryRequest, ServeDetectEngine
    fcfg = cfg.fingerprint
    scfg = serve_config()
    lag = fcfg.lag_samples
    win = 2 * fcfg.window_samples            # a few dozen fingerprints
    wf = ds.waveforms
    reqs = []
    for i in range(N_QUERIES):
        ev = i % len(ds.event_times)
        st = (i // len(ds.event_times)) % wf.shape[0]
        centre = int(ds.arrival_time(ev, st) * fcfg.fs)
        # lag-grid aligned so query fingerprints coincide with stored ones
        lo = min(max(0, centre - win // 2), wf.shape[1] - win) // lag * lag
        reqs.append(QueryRequest(rid=i, window=wf[st, lo:lo + win]))
    eng = ServeDetectEngine.from_detector(
        det, n_slots=scfg.n_slots, top_k=scfg.top_k,
        max_queue=scfg.max_queue)
    t0 = time.perf_counter()
    out = eng.run(reqs)
    log(f"serving: {out['served']}/{out['requests']} served, "
        f"{out['hit_requests']} hit, {out['dispatches']} dispatches in "
        f"{time.perf_counter() - t0:.2f}s (incl. compile)")
    check(out["served"] == len(reqs), "serving dropped requests")
    check(out["hit_requests"] >= 1, "no serving query hit")


def recall_phase(det, cfg, ds) -> None:
    from repro.core.detect import recall_against_truth
    detections, events, _ = det.finalize()
    rec = recall_against_truth(detections, events, ds, cfg.fingerprint)
    log(f"monitoring recall {json.dumps(rec)}, network detections "
        f"{int(detections['valid'].sum())}")


def one_chip(jax, jnp, np) -> None:
    from repro.configs import fast_seismic
    from repro.core.detect import station_stats
    cfg, scfg = fast_seismic.config(), fast_seismic.stream_config()
    dev = jax.devices()[0]

    t0 = time.perf_counter()
    kernel_phase(jax, jnp, np, cfg, scfg)
    log(f"kernels: {time.perf_counter() - t0:.2f}s")

    ds = make_network(np, N_STATIONS)
    t0 = time.perf_counter()
    med, mad = (np.asarray(x) for x in station_stats(ds.waveforms,
                                                      cfg.fingerprint))
    log(f"data: {ds.waveforms.shape} samples, {len(ds.event_times)} "
        f"events; offline statistics {time.perf_counter() - t0:.2f}s")
    precision_phase(jax, jnp, np, cfg, ds.waveforms[0], med[0], mad[0])

    det, raw = monitoring_phase(jax, np, cfg, scfg, ds, med, mad)
    log(f"peak device bytes after monitoring {peak_bytes(dev)}")
    streamed = reference_phase(jax, jnp, np, cfg, scfg, det, ds.waveforms,
                               med, mad, raw)
    backfill_phase(np, cfg, scfg, ds, streamed)
    serving_phase(np, det, cfg, ds)
    recall_phase(det, cfg, ds)
    log(f"peak device bytes {peak_bytes(dev)}")


def four_chips(jax, np) -> None:
    """8 stations: sharded pool over the 4-chip mesh vs the vmap pool."""
    from repro.configs import fast_seismic
    from repro.core.detect import station_stats
    from repro.stream.engine import StreamingDetector, ingest_chunks
    cfg, scfg = fast_seismic.config(), fast_seismic.stream_config()
    ds = make_network(np, 2 * N_STATIONS)
    wf = ds.waveforms
    med, mad = (np.asarray(x) for x in station_stats(wf, cfg.fingerprint))
    adv = scfg.block_fingerprints * cfg.fingerprint.lag_samples
    n_chunks = -(-wf.shape[1] // adv)

    def run(sharded: bool):
        det = StreamingDetector(
            cfg, dataclasses.replace(scfg, sharded=sharded),
            n_stations=wf.shape[0], med_mad=(med, mad))
        walls = det.telemetry.capture_raw_walls()["fused_step"]
        t0 = time.perf_counter()
        ingest_chunks(det, wf, n_chunks=n_chunks)
        det.flush()
        pairs = [sorted(map(tuple, np.concatenate(st.filter.buf).tolist()))
                 if st.filter.buf else [] for st in det.stations]
        log(f"{'sharded' if sharded else 'vmap'} pool: mesh "
            f"{None if det.mesh is None else det.mesh.devices.size} "
            f"devices, {time.perf_counter() - t0:.2f}s, first two steps "
            f"(incl. compile) {walls[0]:.2f}s {walls[1]:.2f}s, later steps "
            f"median {np.median(walls[2:]) * 1e3:.1f}ms, pairs "
            f"{[len(p) for p in pairs]}")
        return det, pairs

    det, sharded = run(True)
    check(det.mesh is not None and det.mesh.devices.size == 4,
          "the sharded pool did not build a 4-device mesh")
    per_dev = {d.id: 0 for d in jax.devices()}
    shard_bytes = dict(per_dev)
    for shard in det.pstate.index.sig.addressable_shards:
        per_dev[shard.device.id] = shard.data.shape[0]
    for leaf in jax.tree.leaves(det.pstate.index):
        for shard in leaf.addressable_shards:
            shard_bytes[shard.device.id] += shard.data.nbytes
    in_use = {d.id: int((d.memory_stats() or {}).get("bytes_in_use", -1))
              for d in jax.devices()}
    state = sum(x.nbytes for x in jax.tree.leaves(det.pstate.index))
    log(f"sharded pool: stations per device {per_dev}, pool state "
        f"{state / 1e6:.1f} MB, its shard bytes per device {shard_bytes}, "
        f"bytes in use per device {in_use}")
    check(set(per_dev.values()) == {wf.shape[0] // 4}
          and set(shard_bytes.values()) == {state // 4},
          "pool state is not split evenly over the four devices")
    check(min(in_use.values()) >= state // 4,
          "a device holds less than its share of the pool state")
    del det
    _, vmap = run(False)
    check(sharded == vmap, "sharded and vmap pool pairs differ")
    log(f"sharded == vmap pairs on all {wf.shape[0]} stations")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded-pool phase on a 4-chip "
                         "host")
    args = ap.parse_args(argv)
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        # the precision phase compares against the CPU backend in-process
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    if args.chips == 1:
        # the one-chip run sees one chip, whatever the host holds
        os.environ.setdefault("TPU_VISIBLE_CHIPS", "0")
        os.environ.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
        os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")
    import jax
    import jax.numpy as jnp
    import numpy as np

    info = device_phase(jax, args.chips)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    log(f"compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(jax, jnp, np)
    else:
        four_chips(jax, np)
    log(f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        sys.exit(1)
