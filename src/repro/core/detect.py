"""End-to-end FAST detection (paper Figure 2) — one core, two drivers.

There is exactly ONE guarded detection core in this repo: the streaming
fingerprint → Min-Max hash → expire/guards → insert/query chain behind
``stream.fused`` / ``stream.index.guarded_step``. This module is the
*batch* driver over it (the QuakeFlow lesson — Zhu et al. 2022: one
workflow serves both archive reprocessing and real-time monitoring):

``detect_events``
    replays an archive trace through the vmapped station-pool step
    (``stream.fused.pool_step_block``): stations are stacked on a leading
    S axis and every block of fingerprints costs ONE pooled dispatch —
    fingerprinting, hashing and index search fused into a single traced
    program — instead of the legacy host loop's four blocking syncs per
    station per stage. Every data-quality guard the streaming service has
    (gap masks, duplicate probe, saturation quarantine, the in-dispatch
    §6.5 occurrence limiter) is therefore available to batch reprocessing
    for free through the same ``StreamConfig`` knobs. The legacy
    per-station fingerprint→signatures→search→filter chain is deleted;
    its exact output is golden-pinned (``tests/golden/batch_detect.json``,
    regenerable via ``scratch/gen_golden_batch.py``) and the replay
    reproduces it bit-exactly.

``detect_step`` / ``detect_step_sharded``
    the fixed-shape jittable cell used by the production-mesh dry-run,
    now a thin wrapper over the same shared core: one
    ``index.guarded_step`` over a fresh in-trace index instead of a
    separate sort-based search implementation.

Stage wall times: the fused replay dispatch covers fingerprint + hash +
search in one program, so ``StageTimes`` attributes it ONCE — to its own
``fused_step_s`` stage — rather than pretending to split it;
``fingerprint_s`` is the §5.2 statistics pass (the two-pass structure's
first pass), ``hashgen_s`` the hash-mapping construction, ``align_s`` the
host tail (§6.5 reference filter + clustering + network association).
``search_s`` remains as a read-only legacy alias of ``fused_step_s`` for
the golden comparisons and older callers. The attribution itself is
derived from the ``repro.obsv`` span layer: ``detect_events`` brackets
each stage in a :class:`~repro.obsv.spans.SpanTracer` span and reads the
per-name totals back, so batch replays emit the same structured trace
(JSONL / ``jax.profiler``) as the streaming service when given a tracer.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import align as align_mod
from repro.core import fingerprint as fp_mod
from repro.core import lsh as lsh_mod
from repro.core.align import AlignConfig, Events
from repro.core.fingerprint import FingerprintConfig
from repro.core.locate import LocateConfig
from repro.core.lsh import LSHConfig, Pairs


@dataclasses.dataclass(frozen=True)
class DetectConfig:
    fingerprint: FingerprintConfig = FingerprintConfig()
    lsh: LSHConfig = LSHConfig()
    align: AlignConfig = AlignConfig()
    # optional location/magnitude tier (core.locate); None = association
    # stops at the pairwise network stage, bit-identical to pre-locate.
    locate: "LocateConfig | None" = None


@dataclasses.dataclass
class StageTimes:
    """Wall seconds per phase. The fused replay step (fingerprint → hash →
    insert/query as one dispatch) is attributed once, to ``fused_step_s``;
    ``search_s`` is a read-only legacy alias of it."""

    fingerprint_s: float = 0.0   # §5.2 statistics pass (stats, not bits)
    hashgen_s: float = 0.0       # hash-mapping construction
    fused_step_s: float = 0.0    # fused replay: all per-block device work
    align_s: float = 0.0         # §6.5 filter + clustering + association

    @property
    def search_s(self) -> float:
        """Legacy name for the fused replay stage (pre-span attribution
        booked the whole pooled dispatch under 'search')."""
        return self.fused_step_s

    def total(self) -> float:
        return (self.fingerprint_s + self.hashgen_s + self.fused_step_s
                + self.align_s)

    @classmethod
    def from_spans(cls, tracer) -> "StageTimes":
        """Derive stage attribution from the span layer's per-name totals
        (the spans ``detect_events`` enters around each stage)."""
        return cls(fingerprint_s=tracer.total_s("fingerprint_stats"),
                   hashgen_s=tracer.total_s("hashgen"),
                   fused_step_s=tracer.total_s("fused_step"),
                   align_s=tracer.total_s("host_tail"))


def _block(x):
    jax.block_until_ready(x)
    return time.perf_counter()


def _locate_tail(detections: dict, waveforms: np.ndarray,
                 qc_sum: np.ndarray, n_fp: int,
                 station_xy: np.ndarray, cfg: DetectConfig,
                 stats: dict) -> dict:
    """Batch-replay location/magnitude stage: QC-counter station weights
    → migration stack over the associated groups → relative magnitudes
    from whole-trace per-fingerprint peak amplitudes. ``waveforms`` is
    (S, C, T) and ``qc_sum`` (S, C, fields): a station's weight counts
    its components' traffic, and its amplitude is its loudest
    component's. Mutates ``stats`` (adds ``moveout_rejected``) and
    returns a new detections dict with the located columns;
    ``reject_inconsistent`` masks failing groups out of ``valid``."""
    from repro.core import locate as locate_mod
    from repro.stream import index as index_mod
    fcfg = cfg.fingerprint
    n_stations, channels, n_samples = waveforms.shape
    qdicts = [{name: int(qc_sum[st, :, k].sum())
               for k, name in enumerate(index_mod.QC_FIELDS)}
              for st in range(n_stations)]
    weights = locate_mod.station_weights(
        qdicts, [n_samples * channels] * n_stations,
        [n_fp * channels] * n_stations, cfg.locate)
    waveforms = np.fmax.reduce(np.abs(waveforms), axis=1)
    fp_amp = [locate_mod.fingerprint_amplitudes(
        waveforms[st], fcfg.lag_samples, fcfg.window_samples)
        for st in range(n_stations)]

    def amp(st, i):
        a = fp_amp[st]
        return float(a[i]) if 0 <= i < a.size else None

    return locate_mod.attach_location(
        detections, np.asarray(station_xy, np.float32), weights,
        fcfg.lag_samples / fcfg.fs, cfg.locate, amp, stats)


def replay_config(lcfg: LSHConfig, block_fingerprints: int = 256,
                  n_buckets: int = 4096):
    """Default ``StreamConfig`` for batch replay.

    The index bucket window matches the offline sort-based search's rank
    window (``bucket_cap``) so the replayed pair set is the legacy one;
    buckets are sized generously because a batch replay holds the whole
    partition resident (no sliding window).
    """
    from repro.stream.index import StreamIndexConfig
    from repro.stream.ingest import StreamConfig
    return StreamConfig(
        block_fingerprints=block_fingerprints,
        index=StreamIndexConfig(n_buckets=n_buckets,
                                bucket_cap=lcfg.bucket_cap))


def station_stats(waveforms: np.ndarray, fcfg: FingerprintConfig
                  ) -> tuple[jax.Array, jax.Array]:
    """Offline §5.2 statistics of every station, (S, n_coeff) median and
    MAD — the first pass of the two-pass structure, with the per-station
    sampling key ``detect_events`` has always used. A streaming detector
    given these (``med_mad``) runs the same per-station binarization as
    the batch replay."""
    meds, mads = [], []
    for st in range(waveforms.shape[0]):
        coeffs = fp_mod.coeffs_from_waveform(jnp.asarray(waveforms[st]),
                                             fcfg)
        med, mad = fp_mod.mad_stats(coeffs, fcfg.mad_sample_rate,
                                    jax.random.PRNGKey(fcfg.stft_len + st))
        meds.append(med)
        mads.append(mad)
    return jnp.stack(meds), jnp.stack(mads)


def detect_events(waveforms: np.ndarray, cfg: DetectConfig,
                  n_partitions: int = 1, scfg=None,
                  keep_pairs: bool = False,
                  tracer=None,
                  station_xy: np.ndarray | None = None
                  ) -> tuple[dict, list[Events],
                             StageTimes, dict]:
    """(S, C, T) waveforms — S stations of C components each, or (S, T)
    for one component — → network detections, via the streaming core
    (batch = replay).

    Every component is a stream of the pool step; per station, each
    component's pairs pass the §6.5 occurrence filter, then the
    similarity of each (dt, idx1) is summed over the components and the
    sum thresholded (``merge_channels``, §7.1 channel level) before
    ``cluster_station``, and the association counts stations.

    Returns (network detections dict, per-station events, stage wall
    times, aggregate stats). ``scfg`` (a ``StreamConfig``) sizes the
    replay blocks/index and switches on any of the streaming data-quality
    guards for archive reprocessing; the default reproduces the legacy
    host-loop output bit-exactly. ``n_partitions`` is accepted for API
    compatibility: the replay is partition-bounded by construction (the
    resident index *is* the §6.4 working-set bound), so the knob is a
    no-op. ``keep_pairs`` stashes each stream's post-filter ``Pairs``
    (station-major, component-minor) under ``stats["_station_pairs"]``
    (the golden-pin hook).

    Stage attribution goes through the span layer: each stage runs inside
    a :class:`~repro.obsv.spans.SpanTracer` span and ``StageTimes`` is
    read back from the per-name totals. Pass ``tracer`` (e.g. one built
    with ``jsonl_path=...`` or ``profile_dir=...``) to capture the
    structured trace; by default a private tracer provides the totals
    only. With ``cfg.locate`` set and ``station_xy`` (S, 2) given, the
    association output additionally carries migration-located origins,
    moveout-consistency flags and relative magnitudes (see
    :mod:`repro.core.locate`); groups failing the moveout check are
    masked out of ``valid`` when ``cfg.locate.reject_inconsistent``.
    With ``scfg.telemetry`` on (the default), the replay also collects
    the in-dispatch ``index.QC_FIELDS`` counters — summed over blocks
    into ``stats["drops"]`` (per guard, summed over streams) with
    per-stream vectors under ``stats["station<i>_qc"]`` (stream i) — at
    no extra dispatch. Span wall totals stay on the tracer (deliberately
    out of ``stats``, which is compared dict-exact by the golden tests).
    """
    from repro.obsv.spans import SpanTracer
    from repro.stream import fused as fused_mod
    from repro.stream import index as index_mod
    from repro.stream.engine import align_station, pairs_from_triplets

    waveforms = np.asarray(waveforms, np.float32)
    if waveforms.ndim < 3:
        waveforms = np.atleast_2d(waveforms)[:, None, :]
    n_stations, channels = waveforms.shape[:2]
    station_waves = waveforms
    waveforms = waveforms.reshape(n_stations * channels, -1)
    n_streams = waveforms.shape[0]
    fcfg, lcfg, acfg = cfg.fingerprint, cfg.lsh, cfg.align
    if scfg is None:
        scfg = replay_config(lcfg)
    tracer = tracer or SpanTracer()
    stats: dict = {}
    n_fp = fcfg.n_fingerprints(waveforms.shape[1])

    # §5.2 statistics: the two-pass structure's first pass, with the same
    # per-station sampling key the legacy loop used (bit-exact stats).
    # The fused replay below re-derives each block's coefficients inside
    # its own dispatch, so this pass's whole-trace coefficients are spent
    # on the statistics alone — the price of running the *identical*
    # traced program as the streaming service (which owns no whole-trace
    # buffer to begin with) rather than a batch-only coeffs-in variant
    with tracer.span("fingerprint_stats"):
        meds, mads = station_stats(waveforms, fcfg)
        _block(mads)
    with tracer.span("hashgen"):
        mappings = lsh_mod.hash_mappings(fcfg.fp_dim, lcfg)
        _block(mappings)

    # fused replay: ONE pooled dispatch per block for all S stations;
    # counters ride inside the same dispatch when telemetry is on
    ctr = 1 if getattr(scfg, "telemetry", True) else 0
    mp = getattr(scfg, "max_pairs_per_block", 0)
    ver = getattr(scfg, "verify_code", 0)
    mj = getattr(scfg, "verify_min_jaccard", 0.0)
    icfg = (scfg.effective_index(fcfg.fp_dim)
            if hasattr(scfg, "effective_index") else scfg.index)
    qc_sum = np.zeros((n_streams, len(index_mod.QC_FIELDS)), np.int64)
    state = fused_mod.init_pool_state(
        [index_mod.init_index(lcfg, icfg) for _ in range(n_streams)],
        fcfg.halo_samples, meds, mads)
    b = scfg.block_fingerprints
    bs = fcfg.block_samples(b)
    tri: list[list[np.ndarray]] = [[] for _ in range(n_streams)]
    for base in range(0, n_fp, b):
        with tracer.span("fused_step", base=base):
            n_valid = min(b, n_fp - base)
            start = base * fcfg.lag_samples
            block = np.zeros((n_streams, bs), np.float32)
            seg = waveforms[:, start:start + bs]
            block[:, :seg.shape[1]] = seg
            vmask = np.broadcast_to(np.arange(b) < n_valid,
                                    (n_streams, b))
            state, pairs, qc = fused_mod.pool_step_block(
                state, jnp.asarray(block), mappings, jnp.int32(base),
                jnp.asarray(vmask), fcfg, lcfg, scfg.window_fingerprints,
                scfg.saturation_limit, scfg.dup_sig_tables, scfg.occ_limit,
                ctr, mp, ver, mj)
            # one transfer + one sync for the whole pooled step output
            (i1, i2, sim, pv), qc = jax.device_get(
                ((pairs.idx1, pairs.idx2, pairs.sim, pairs.valid), qc))
            qc_sum += np.asarray(qc, np.int64)
            for st in range(n_streams):
                m = pv[st]
                if m.any():
                    tri[st].append(np.stack(
                        [i1[st][m], i2[st][m], sim[st][m]],
                        axis=1).astype(np.int64))

    # host tail: §6.5 reference filter + channel merge + clustering,
    # shared with the streaming finalize
    with tracer.span("host_tail"):
        station_events: list[Events] = []
        station_pairs: list[Pairs] = []   # per stream, post-filter
        for st in range(n_stations):
            events, comps, excluded = align_station(
                [pairs_from_triplets(np.concatenate(tri[c], axis=0)
                                     if tri[c] else np.zeros((0, 3)))
                 for c in range(st * channels, (st + 1) * channels)],
                n_fp, cfg)
            if lcfg.occurrence_frac > 0 and n_fp > 0:
                stats[f"station{st}_excluded"] = excluded
            stats[f"station{st}_pairs"] = sum(int(p.count()) for p in comps)
            stats[f"station{st}_fingerprints"] = n_fp
            stats[f"station{st}_events"] = int(events.count())
            station_events.append(events)
            station_pairs.extend(comps)

        with_locate = cfg.locate is not None and station_xy is not None \
            and n_stations >= 2
        detections = align_mod.associate_network(
            station_events, acfg, n_stations, with_onsets=with_locate)
        jax.block_until_ready(detections["valid"])
        if with_locate:
            detections = _locate_tail(
                detections, station_waves, qc_sum.reshape(
                    n_stations, channels, -1), n_fp, station_xy, cfg, stats)
    times = StageTimes.from_spans(tracer)
    stats["detections"] = int(np.asarray(detections["valid"]).sum())
    if ctr:
        stats["drops"] = {
            name: int(qc_sum[:, k].sum())
            for k, name in enumerate(index_mod.QC_FIELDS)}
        for st in range(n_streams):
            stats[f"station{st}_qc"] = {
                name: int(qc_sum[st, k])
                for k, name in enumerate(index_mod.QC_FIELDS)}
    if keep_pairs:
        stats["_station_pairs"] = station_pairs
    return detections, station_events, times, stats


# ---------------------------------------------------------------------------
# jittable core for distributed execution / dry-run
# ---------------------------------------------------------------------------


def detect_step(waveform_chunk: jax.Array, med: jax.Array, mad: jax.Array,
                cfg: DetectConfig, icfg=None, window: int = 0,
                saturation: int = 0, dup_tables: int = 0,
                occ_limit: int = 0) -> dict:
    """One shard's detection step (fixed shapes, jittable) — a wrapper
    over the shared streaming core.

    ``waveform_chunk``: (chunk_samples,) — includes halo so fingerprint
    counts are static. MAD statistics are precomputed global (two-pass
    structure, §5.2). The chunk's fingerprints go through one
    ``index.guarded_step`` against a fresh in-trace index (the same
    insert/query, guard and limiter program as the streaming hot path —
    no separate batch search implementation), then the host-reference
    §6.5 filter and clustering. The quality knobs (``saturation``,
    ``dup_tables``, ``occ_limit``) default off; ``icfg`` sizes the
    in-trace index (``occ_limit`` > 0 needs ``icfg.occ_slots``).
    Returns triplets + events for downstream alignment.
    """
    from repro.stream import index as index_mod
    fcfg, lcfg, acfg = cfg.fingerprint, cfg.lsh, cfg.align
    if icfg is None:
        from repro.stream.index import StreamIndexConfig
        icfg = StreamIndexConfig(n_buckets=4096, bucket_cap=lcfg.bucket_cap)
    assert occ_limit == 0 or icfg.occ_slots > 0, \
        "occ_limit needs icfg.occ_slots (the partner-count ring)"
    bits, _ = fp_mod.fingerprints_from_waveform(
        waveform_chunk, fcfg, med_mad=(med, mad))
    n = bits.shape[0]
    mappings = lsh_mod.hash_mappings(fcfg.fp_dim, lcfg)
    sigs, buckets = lsh_mod.signatures_and_buckets(bits, mappings, lcfg,
                                                   icfg.n_buckets)
    ids = jnp.arange(n, dtype=jnp.int32)
    _, pairs, _ = index_mod.guarded_step(
        index_mod.init_index(lcfg, icfg), sigs, buckets, ids, None, lcfg,
        window, saturation=saturation, dup_tables=dup_tables,
        occ_limit=occ_limit)
    if lcfg.occurrence_frac > 0:
        pairs, _ = lsh_mod.occurrence_filter(pairs, n, lcfg.occurrence_frac)
    events = align_mod.cluster_station(pairs, acfg)
    return {
        "dt": pairs.dt, "idx1": pairs.idx1, "sim": pairs.sim,
        "pair_valid": pairs.valid,
        "ev_dt": events.dt, "ev_onset": events.onset,
        "ev_score": events.score, "ev_valid": events.valid,
    }


def detect_step_sharded(waveforms: jax.Array, med: jax.Array,
                        mad: jax.Array, cfg: DetectConfig, mesh,
                        **knobs) -> dict:
    """Chunk-parallel detect_step under shard_map (DESIGN.md §3.7).

    The per-chunk pipeline is embarrassingly parallel (the paper's §6.4
    partition structure), but the XLA partitioner lowers vmapped
    segment-sums / top_k over a sharded chunk axis to involuntary
    all-gathers of the whole buffer. shard_map pins each chunk's work to
    its device: zero collectives by construction. ``knobs`` forward the
    quality/limiter parameters to ``detect_step``.
    """
    import functools

    from jax.sharding import PartitionSpec as P

    all_axes = tuple(a for a in ("pod", "data", "model")
                     if a in mesh.shape)
    step = jax.vmap(functools.partial(detect_step, cfg=cfg, **knobs),
                    in_axes=(0, None, None))

    def per_shard(wf, md, md2):
        return step(wf, md, md2)

    return jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(all_axes, None), P(), P()),
        out_specs=P(all_axes),
        check_vma=False)(waveforms, med, mad)


def recall_against_truth(detections: dict, station_events: list[Events],
                         dataset, fcfg: FingerprintConfig,
                         tol_s: float = 6.0) -> dict:
    """Fraction of injected reoccurring events recovered (any station).

    An injected event counts as detected if some station-level event onset
    falls within ``tol_s`` of its arrival time at that station.
    """
    lag_s = fcfg.lag_samples / fcfg.fs
    hit = np.zeros(len(dataset.event_times), bool)
    for st, ev in enumerate(station_events):
        onsets = np.asarray(ev.onset)[np.asarray(ev.valid)]
        extents = np.asarray(ev.extent)[np.asarray(ev.valid)]
        if onsets.size == 0:
            continue
        # each cluster covers [onset, onset+extent] on idx1 and the partner
        # occurrence at idx1+dt; check both ends
        dts = np.asarray(ev.dt)[np.asarray(ev.valid)]
        cand_times = np.concatenate([
            onsets * lag_s, (onsets + extents) * lag_s,
            (onsets + dts) * lag_s])
        for i in range(len(dataset.event_times)):
            at = dataset.arrival_time(i, st)
            if np.any(np.abs(cand_times - at) < tol_s):
                hit[i] = True
    # an event is only *detectable* if its source reoccurs
    src, cnt = np.unique(dataset.event_sources, return_counts=True)
    detectable = np.isin(dataset.event_sources, src[cnt >= 2])
    n_det = int(detectable.sum())
    return {
        "recall": float(hit[detectable].sum() / max(n_det, 1)),
        "hits": int(hit[detectable].sum()),
        "detectable": n_det,
        "n_events": len(dataset.event_times),
    }
