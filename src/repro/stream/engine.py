"""Streaming detector: ring → fingerprints → index → pairs → events.

``StationStream`` owns one station's ingestion state: a ``WaveformRing``
(chunk framing + halo), a ``StreamingMAD`` (running §5.2 statistics), and
the device-resident detection state. Each ready block runs one jitted
fixed-shape step — fingerprint, sign, expire, insert, query — and the
emitted pairs either accumulate host-side (parity mode) or flow through a
``RollingPairFilter`` (bounded mode). ``StreamingDetector`` composes
stations and finishes with the *same* alignment stack as the offline path
(occurrence filter → channel merge → ``cluster_station`` → network
association), so a streamed trace yields the same detections as a batch
re-run, at O(chunk) cost per arrival instead of O(history).

Hot path anatomy (the one-dispatch invariant, ISSUE 3): with
``StreamConfig.fused`` (the default) the steady-state per-block work is a
**single** ``jax.jit`` dispatch — ``fused.step_advance`` — whose input is
only the block's *new* samples and whose entire state (index tables, ring
halo, MAD statistics) is a donated ``FusedState`` pytree reused in place
chunk after chunk. Multi-station detectors additionally run **pooled**:
all S stations' states are stacked on a leading axis and stepped through
one vmapped executable (``fused.pool_step_advance``), so S stations cost
one dispatch, not S. See ``stream/fused.py`` for the full anatomy and
``tests/test_stream.py`` for the parity / retracing / donation guards
that pin it. ``fused=False`` keeps the PR-1/2 multi-call chain
(``block_coeffs`` + ``stream_step``) as the bit-exact parity reference.

Two memory regimes, selected by ``StreamConfig``:

* **parity mode** (defaults): every emitted triplet is kept until
  ``finalize`` runs the offline occurrence filter + clustering over the
  full accumulation — exact offline semantics, O(stream) host state.
* **bounded mode** (``window_fingerprints`` + ``filter_window_fingerprints``
  > 0): the jitted step expires index entries older than the sliding
  window, and triplets are retired window-by-window through the rolling
  occurrence filter into compact event rows — O(window) host state for an
  unbounded stream (the paper's §5.3/§6.5 partition-bounded post-processing
  made continuous). Clusters split at a filter-window boundary are
  re-merged by ``merge_boundary_rows`` before any consumer sees them.
  With ≥2 stations, ``poll_detections`` additionally associates
  closed-window events across stations after every push, so network
  detections surface near-real-time instead of only at finalize.

``snapshot``/``restore`` checkpoint the whole detector (index pytree, ring,
reservoir, pending blocks, rolling-filter state) through
``train/checkpoint.py``: a killed service restored from its last snapshot
reproduces the uninterrupted run's detections exactly.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import dist
from repro.core import align as align_mod
from repro.core import fingerprint as fp_mod
from repro.core import locate as locate_mod
from repro.core import lsh as lsh_mod
from repro.core.align import AlignConfig, Events
from repro.core.detect import DetectConfig
from repro.core.fingerprint import FingerprintConfig
from repro.core.lsh import INVALID, LSHConfig, Pairs
from repro.obsv.metrics import merge_counts
from repro.stream import fused as fused_mod
from repro.stream import index as index_mod
from repro.stream import telemetry as tele_mod
from repro.stream.index import IndexState
from repro.stream.ingest import StreamConfig, StreamingMAD, WaveformRing
from repro.stream.telemetry import StreamTelemetry
from repro.train import checkpoint as ckpt_mod


@functools.partial(jax.jit, static_argnames=("fcfg",))
def block_coeffs(block: jax.Array, fcfg: FingerprintConfig) -> jax.Array:
    """(block_samples,) → (block_fp, n_coeff) Haar coefficients."""
    return fp_mod.coeffs_from_waveform(block, fcfg)


@functools.partial(jax.jit, static_argnames=("fcfg",))
def pool_block_coeffs(blocks: jax.Array,
                      fcfg: FingerprintConfig) -> jax.Array:
    """(S, block_samples) → (S, block_fp, n_coeff) coefficients (one
    dispatch for the whole station pool's warmup)."""
    return jax.vmap(lambda b: fp_mod.coeffs_from_waveform(b, fcfg))(blocks)


@functools.partial(jax.jit, static_argnames=("fcfg", "lcfg", "window",
                                             "saturation", "dup_tables",
                                             "occ_limit", "counters",
                                             "max_pairs", "verify",
                                             "min_jac"),
                   donate_argnums=(0,))
def stream_step(state: IndexState, coeffs: jax.Array, med: jax.Array,
                mad: jax.Array, mappings: jax.Array, base_id: jax.Array,
                valid: jax.Array, fcfg: FingerprintConfig, lcfg: LSHConfig,
                window: int = 0, saturation: int = 0, dup_tables: int = 0,
                occ_limit: int = 0, counters: int = 0, max_pairs: int = 0,
                verify: int = 0, min_jac: float = 0.0
                ) -> tuple[IndexState, Pairs, jax.Array]:
    """One fixed-shape streaming step: binarize → sign → expire → guards →
    insert → query. (The *unfused* half of the PR-1/2 chain — kept as the
    parity reference and benchmark baseline for the fused step.)

    Same-shape blocks reuse one executable (base_id and the valid mask are
    traced, configs, the window length and the quality knobs are static);
    insert-then-query with the id-ordered emission rule yields each
    (earlier, later) pair exactly once per colliding table. Invalid rows
    (zero-padded flush tails, gap-masked fingerprints) get unique filler
    signatures, are not stored, and cannot match.

    ``window`` > 0 expires index entries older than the newest id in this
    block minus the window *before* inserting it, so every emitted pair
    satisfies idx2 - idx1 < window — the sliding detection window. The
    expire/guard/insert/query tail is ``index.guarded_step``, shared with
    the fused path, so the two hot paths stay bit-identical with the
    quality guards on or off.
    """
    bits, packed = fp_mod.binarize_coeffs(coeffs, fcfg, (med, mad))
    sigs, buckets = lsh_mod.signatures_and_buckets(
        bits, mappings, lcfg, state.shape[1], valid=valid)
    ids = base_id + jnp.arange(sigs.shape[0], dtype=jnp.int32)
    return index_mod.guarded_step(state, sigs, buckets, ids, valid, lcfg,
                                  window, saturation=saturation,
                                  dup_tables=dup_tables,
                                  occ_limit=occ_limit, counters=counters,
                                  packed=packed if verify > 0 else None,
                                  max_pairs=max_pairs, verify=verify,
                                  min_jac=min_jac)


def pairs_from_triplets(tri: np.ndarray, pad_to: int = 1024) -> Pairs:
    """(m, 3) host triplets (idx1, idx2, sim) → masked fixed-size ``Pairs``.

    Padded to a multiple of ``pad_to`` so downstream jitted consumers see
    few distinct shapes.
    """
    tri = np.asarray(tri).reshape(-1, 3)
    m = tri.shape[0]
    size = max(pad_to, -(-max(m, 1) // pad_to) * pad_to)
    idx1 = np.full(size, INVALID, np.int32)
    idx2 = np.full(size, INVALID, np.int32)
    sim = np.zeros(size, np.int32)
    val = np.zeros(size, bool)
    idx1[:m] = tri[:, 0]
    idx2[:m] = tri[:, 1]
    sim[:m] = tri[:, 2]
    val[:m] = True
    return Pairs(idx1=jnp.asarray(idx1), idx2=jnp.asarray(idx2),
                 sim=jnp.asarray(sim), valid=jnp.asarray(val))


# alert row layout: (dt, onset, n_stations, score, upgrade, x_mkm, y_mkm,
# mag_milli) — locations in milli-km (LOC_NONE without a locate tier),
# magnitudes in milli-magnitudes (MAG_NONE when no amplitude is in hand),
# upgrade=1 on a re-emission whose station multiplicity grew
ALERT_COLS = 8


def events_to_rows(events: Events) -> np.ndarray:
    """Valid entries of an ``Events`` pytree → compact (k, 5) int64 rows
    (dt, onset, extent, size, score)."""
    v = np.asarray(events.valid)
    return np.stack(
        [np.asarray(events.dt)[v], np.asarray(events.onset)[v],
         np.asarray(events.extent)[v], np.asarray(events.size)[v],
         np.asarray(events.score)[v]], axis=1).astype(np.int64)


def events_from_rows(rows: np.ndarray, pad_to: int = 256) -> Events:
    """(k, 5) rows → masked ``Events`` padded to a multiple of ``pad_to``."""
    rows = np.asarray(rows, np.int64).reshape(-1, 5)
    k = rows.shape[0]
    size = max(pad_to, -(-max(k, 1) // pad_to) * pad_to)
    full = np.zeros((size, 5), np.int64)
    full[:k] = rows
    val = np.arange(size) < k
    fill = np.where(val, 0, INVALID)
    return Events(
        dt=jnp.asarray((full[:, 0] + fill).astype(np.int32)),
        onset=jnp.asarray((full[:, 1] + fill).astype(np.int32)),
        extent=jnp.asarray(full[:, 2].astype(np.int32)),
        size=jnp.asarray(full[:, 3].astype(np.int32)),
        score=jnp.asarray(full[:, 4].astype(np.int32)),
        valid=jnp.asarray(val))


def merge_boundary_rows(rows: np.ndarray, acfg: AlignConfig) -> np.ndarray:
    """Re-merge event rows split at rolling-filter window boundaries.

    Bounded-mode clustering closes per filter window, so a diagonal
    cluster straddling a boundary surfaces as two rows: (nearly) the same
    dt, abutting idx ranges. This pass re-joins rows whose dt differ by at
    most ``dt_merge_tol`` and whose [onset, onset + extent] spans are
    within ``gap`` of each other — the same criteria ``cluster_station``
    uses for its in-window merge, applied across windows. Host-side and
    O(k log k) in the (small) number of event rows; runs before any
    consumer (association feed, finalize) sees the rows.
    """
    rows = np.asarray(rows, np.int64).reshape(-1, 5)
    k = rows.shape[0]
    if k <= 1:
        return rows
    order = np.lexsort((rows[:, 0], rows[:, 1]))  # by (onset, dt)
    rows = rows[order]
    dt, onset, ext = rows[:, 0], rows[:, 1], rows[:, 2]
    end = onset + ext
    # union-find over pairwise near-edges between the ORIGINAL rows: the
    # merge criteria are evaluated on unmerged rows only (no mid-pass
    # mutation), so the result is independent of encounter order, and a
    # chain of ≥3 straddling rows collapses into one component instead of
    # first-match-only partial merges.
    parent = np.arange(k)

    def find(i: int) -> int:
        while parent[i] != i:
            parent[i] = parent[parent[i]]   # path halving
            i = parent[i]
        return i

    for i in range(k):
        for j in range(i + 1, k):
            apart = int(onset[j]) - int(end[i])
            if apart > acfg.gap:
                break            # onsets monotone: no later j can be near
            if abs(int(dt[i]) - int(dt[j])) <= acfg.dt_merge_tol:
                ri, rj = find(i), find(j)
                if ri != rj:
                    parent[max(ri, rj)] = min(ri, rj)
    roots = np.fromiter((find(i) for i in range(k)), np.int64, k)
    out: list[np.ndarray] = []
    for r in np.unique(roots):               # root order == onset order
        m = roots == r
        # representative dt: the highest-score member's ORIGINAL dt
        # (ties → earliest in the onset sort), matching the in-window
        # merge's strongest-diagonal convention
        rep = np.nonzero(m)[0][np.argmax(rows[m, 4])]
        out.append(np.array([dt[rep], onset[m].min(),
                             end[m].max() - onset[m].min(),
                             rows[m, 3].sum(), rows[m, 4].sum()], np.int64))
    return np.stack(out, axis=0)


def host_occurrence_filter(pairs: Pairs, n_fp: int, lcfg: LSHConfig, *,
                           base: int = 0, limit: int | None = None
                           ) -> tuple[Pairs, jax.Array]:
    """The host-side §6.5 occurrence filter over an accumulated pair set.

    The one shared invocation behind every host-side call site — the
    parity-mode ``finalize``, the rolling per-window filter, and the
    batch replay driver (``core.detect.detect_events``) — kept as the
    bit-exact reference/fallback for the in-dispatch occurrence limiter
    (``index.occurrence_limit_pairs``). ``base`` rebases ids into a
    static [0, n_fp) span first (the rolling filter's window-local id
    space) and restores the original ids on the way out; ``limit``
    overrides the ``frac * n_fp`` occurrence cap when the partition whose
    fraction is meant differs from the id span. Returns
    (filtered pairs, excluded-fingerprint mask over the rebased span).
    """
    v = pairs.valid
    local = pairs if base == 0 else Pairs(
        idx1=jnp.where(v, pairs.idx1 - base, INVALID),
        idx2=jnp.where(v, pairs.idx2 - base, INVALID),
        sim=pairs.sim, valid=v)
    filt, excluded = lsh_mod.occurrence_filter(
        local, n_fp, lcfg.occurrence_frac, limit=limit)
    if base == 0:
        return filt, excluded
    keep = filt.valid
    return Pairs(idx1=jnp.where(keep, pairs.idx1, INVALID),
                 idx2=jnp.where(keep, pairs.idx2, INVALID),
                 sim=jnp.where(keep, pairs.sim, 0),
                 valid=keep), excluded


class RollingPairFilter:
    """Rolling per-window §6.5 occurrence filter + clustering.

    Every emitted pair is assigned to the window of its *later* member (the
    query id that emitted it). Once the processed-id frontier passes a
    window's end, no further pair can land in it, so the window closes:
    the occurrence filter runs over its pairs with ids rebased into the
    static [w_start - lookback, w_start + window) span (the sliding index
    window guarantees partners reach back at most ``lookback``), survivors
    are channel-merged and diagonal-clustered exactly like finalize, and
    only the resulting compact event rows are retained. Buffered host pair
    state is therefore O(window) for an unbounded stream — the streaming
    analogue of the paper's partition-bounded post-processing. Rows handed
    out (``rows_tail``/``all_rows``) pass the cross-window
    ``merge_boundary_rows`` pass first, so clusters split at a window
    close re-merge before association.
    """

    def __init__(self, cfg: DetectConfig, window: int, lookback: int,
                 pad_to: int = 1024):
        if window <= 0 or lookback <= 0:
            raise ValueError(f"need positive filter window and lookback, "
                             f"got {window}, {lookback}")
        self.cfg = cfg
        self.window = int(window)
        self.lookback = int(lookback)
        self.pad_to = pad_to
        self.w_start = 0
        self.buf: list[np.ndarray] = []     # open-window (m, 3) triplets
        self.buf_rows = 0
        self.peak_rows = 0
        self.event_rows: list[np.ndarray] = []  # closed (k, 5) rows, active
        self.archive_rows: list[np.ndarray] = []  # retired from association
        self.windows_closed = 0
        self.pairs_seen = 0
        self.pairs_kept = 0

    def add(self, tri: np.ndarray) -> None:
        tri = np.asarray(tri).reshape(-1, 3)
        if tri.shape[0]:
            self.buf.append(tri)
            self.buf_rows += tri.shape[0]
            self.peak_rows = max(self.peak_rows, self.buf_rows)
            self.pairs_seen += tri.shape[0]

    def advance(self, frontier: int) -> int:
        """Close every window whose end the processed frontier has passed."""
        closed = 0
        while frontier >= self.w_start + self.window:
            self._close(self.w_start + self.window)
            closed += 1
        return closed

    def close_all(self, frontier: int) -> None:
        """Flush the open tail window (finalize boundary)."""
        self.advance(frontier)
        if self.buf_rows:
            self._close(self.w_start + self.window)

    def rows_tail(self, min_onset: int) -> np.ndarray:
        """Active event rows reaching ``min_onset`` or later (association
        feed), boundary-merged.

        The floor is applied to the *end* of each merged span
        (onset + extent), not the onset: a fresh boundary row merged into
        an older cluster inherits the older onset, and filtering on onset
        would drop the merged row — and with it the fresh contribution —
        from this poll's association.
        """
        if not self.event_rows:
            return np.zeros((0, 5), np.int64)
        rows = merge_boundary_rows(np.concatenate(self.event_rows, axis=0),
                                   self.cfg.align)
        return rows[rows[:, 1] + rows[:, 2] >= min_onset]

    def retire_below(self, min_onset: int) -> None:
        """Move rows the association floor has passed into the archive.

        Retired rows can never alert again (``rows_tail`` already excluded
        them), so keeping them out of the active list makes the per-push
        association scan O(active window), not O(stream). They remain part
        of ``all_rows`` for the authoritative finalize.
        """
        if not self.event_rows:
            return
        rows = np.concatenate(self.event_rows, axis=0)
        old = rows[:, 1] < min_onset
        if not old.any():
            return
        self.archive_rows.append(rows[old])
        keep = rows[~old]
        self.event_rows = [keep] if keep.shape[0] else []

    def all_rows(self) -> np.ndarray:
        rows = self.archive_rows + self.event_rows
        if not rows:
            return np.zeros((0, 5), np.int64)
        return merge_boundary_rows(np.concatenate(rows, axis=0),
                                   self.cfg.align)

    def _close(self, w_end: int) -> None:
        tri = (np.concatenate(self.buf, axis=0) if self.buf
               else np.zeros((0, 3), np.int64))
        in_w = tri[:, 1] < w_end
        cur, rest = tri[in_w], tri[~in_w]
        self.buf = [rest] if rest.shape[0] else []
        self.buf_rows = int(rest.shape[0])
        if cur.shape[0]:
            rows = self._filter_cluster(cur)
            if rows.shape[0]:
                self.event_rows.append(rows)
        self.w_start = w_end
        self.windows_closed += 1

    def _filter_cluster(self, tri: np.ndarray) -> np.ndarray:
        """One window's triplets → occurrence-filtered clustered rows."""
        lcfg, acfg = self.cfg.lsh, self.cfg.align
        pairs = pairs_from_triplets(tri, self.pad_to)
        if lcfg.occurrence_frac > 0:
            pairs, _ = host_occurrence_filter(
                pairs, self.lookback + self.window, lcfg,
                base=self.w_start - self.lookback,
                limit=max(1, int(lcfg.occurrence_frac * self.window)))
        self.pairs_kept += int(pairs.count())
        merged = align_mod.merge_channels(
            [(pairs.dt, pairs.idx1, pairs.sim, pairs.valid)],
            acfg.channel_threshold)
        events = align_mod.cluster_station(merged, acfg)
        return events_to_rows(events)

    def snapshot(self) -> tuple[dict, dict]:
        buf = (np.concatenate(self.buf, axis=0).astype(np.int64)
               if self.buf else np.zeros((0, 3), np.int64))
        rows = self.archive_rows + self.event_rows
        raw = (np.concatenate(rows, axis=0) if rows
               else np.zeros((0, 5), np.int64))
        return ({"buf": buf, "events": raw},
                {"w_start": self.w_start, "windows_closed":
                 self.windows_closed, "pairs_seen": self.pairs_seen,
                 "pairs_kept": self.pairs_kept, "peak_rows": self.peak_rows})

    def restore(self, arrays: dict, scalars: dict) -> None:
        buf = np.asarray(arrays["buf"], np.int64).reshape(-1, 3)
        self.buf = [buf] if buf.shape[0] else []
        self.buf_rows = int(buf.shape[0])
        rows = np.asarray(arrays["events"], np.int64).reshape(-1, 5)
        self.archive_rows = []
        self.event_rows = [rows] if rows.shape[0] else []
        self.w_start = int(scalars["w_start"])
        self.windows_closed = int(scalars["windows_closed"])
        self.pairs_seen = int(scalars["pairs_seen"])
        self.pairs_kept = int(scalars["pairs_kept"])
        self.peak_rows = int(scalars["peak_rows"])


# per-chunk wall samples retained for the percentile view; older samples
# fold into wall_total_s, so host memory is O(1) on unbounded streams
# (the pre-ISSUE-6 list grew with the stream)
WALL_WINDOW = 1024


@dataclasses.dataclass
class StreamStats:
    chunks: int = 0
    blocks: int = 0
    samples: int = 0
    fingerprints: int = 0
    pairs: int = 0
    wall_total_s: float = 0.0
    chunk_wall_s: collections.deque = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=WALL_WINDOW))

    def record_wall(self, dt: float) -> None:
        self.wall_total_s += dt
        self.chunk_wall_s.append(dt)

    def summary(self) -> dict:
        wall = np.asarray(self.chunk_wall_s or [0.0])
        total = float(self.wall_total_s)
        return {
            "chunks": self.chunks,
            "blocks": self.blocks,
            "samples": self.samples,
            "fingerprints": self.fingerprints,
            "pairs": self.pairs,
            "wall_s": round(total, 4),
            # percentiles over the rolling window (recent behavior)
            "chunk_ms_p50": round(float(np.percentile(wall, 50)) * 1e3, 3),
            "chunk_ms_p95": round(float(np.percentile(wall, 95)) * 1e3, 3),
            "chunks_per_s": round(self.chunks / max(total, 1e-9), 2),
            "samples_per_s": round(self.samples / max(total, 1e-9), 1),
        }


class StationStream:
    """Incremental detection state for a single station.

    ``external=True`` (set by a pooled ``StreamingDetector``) keeps only
    host-side state here — ring framing, reservoir, rolling filter,
    stats — while the owner steps the device state through the vmapped
    station pool and feeds this station's slice back via ``_consume``.
    """

    def __init__(self, cfg: DetectConfig, scfg: StreamConfig,
                 med_mad: tuple[np.ndarray, np.ndarray] | None = None,
                 external: bool = False,
                 telemetry: StreamTelemetry | None = None):
        self.cfg = cfg
        self.scfg = scfg
        # detector-shared telemetry hub; a standalone station gets its own
        self.telemetry = telemetry or StreamTelemetry(1)
        fcfg, lcfg = cfg.fingerprint, cfg.lsh
        self.external = external
        self.fused = scfg.fused
        self.ring = WaveformRing(fcfg, scfg.block_fingerprints,
                                 reorder_horizon=scfg.reorder_horizon_samples,
                                 max_gap=scfg.max_gap_samples)
        self.mad = StreamingMAD(scfg.reservoir_rows, fcfg.n_coeff,
                                seed=scfg.seed)
        # pk_words resolved against this detector's fingerprint dim so
        # the verify ring rows match what the binarizer packs
        self.icfg = scfg.effective_index(fcfg.fp_dim)
        self._state: IndexState | None = index_mod.init_index(lcfg,
                                                              self.icfg)
        self.mappings = lsh_mod.hash_mappings(fcfg.fp_dim, lcfg)
        self.fstate: fused_mod.FusedState | None = None
        self._halo_ok = False
        self._med_mad: tuple[jax.Array, jax.Array] | None = None
        self._owner = None          # pooled detector backref (+ index)
        self._pool_idx = 0
        if med_mad is not None:
            self._set_frozen(med_mad[0], med_mad[1])
        # (base_id, block, coeffs-or-None, gap_mask-or-None)
        self.pending: list[tuple[int, np.ndarray, jax.Array | None,
                                 np.ndarray | None]] = []
        # in-dispatch guard counters (ring.quality covers the ingest
        # side). suppressed_fingerprints counts every fingerprint masked
        # out of the dispatch for ANY reason — gap overlap or duplicate
        # flag — so it is a superset of duplicate_fingerprints; the
        # gap-specific volume is ring.quality's gap/missing counters.
        self.qc = {"duplicate_fingerprints": 0, "saturated_lookups": 0,
                   "suppressed_fingerprints": 0, "limited_pairs": 0}
        # sample-exact repeated-segment detector state (window hashes of
        # the last dup_window_fingerprints fingerprints)
        self.dup_window = scfg.dup_window_fingerprints
        self._dup_hist: collections.deque[tuple[int, int]] = \
            collections.deque()
        self._dup_map: dict[int, int] = {}   # hash -> newest fp id
        self.triplets: list[np.ndarray] = []            # (m, 3) idx1,idx2,sim
        self.rolling = scfg.filter_window_fingerprints > 0
        self.filter = (RollingPairFilter(cfg, scfg.filter_window_fingerprints,
                                         scfg.window_fingerprints)
                       if self.rolling else None)
        self.processed_fp = 0       # ids fully through the jitted step
        self._tri_rows = 0
        self.peak_tri_rows = 0
        self.stats = StreamStats()

    # -- device-state views --------------------------------------------------

    @property
    def state(self) -> IndexState:
        """This station's index state, wherever it currently lives."""
        if self.fstate is not None:
            return self.fstate.index
        if self._owner is not None and self._owner.pstate is not None:
            return index_mod.slice_state(self._owner.pstate.index,
                                         self._pool_idx)
        assert self._state is not None, "station has no device state"
        return self._state

    @property
    def med_mad(self) -> tuple[jax.Array, jax.Array] | None:
        return self._med_mad

    @property
    def stats_frozen(self) -> bool:
        return self._med_mad is not None

    def _set_frozen(self, med, mad) -> None:
        self._med_mad = (jnp.asarray(med), jnp.asarray(mad))
        if self.fused and not self.external:
            self.fstate = fused_mod.init_state(
                self._state, self.cfg.fingerprint.halo_samples, med, mad)
            self._state = None      # the fused state owns the buffers now
            self._halo_ok = False

    def host_state_rows(self) -> int:
        """Candidate triplet rows currently buffered host-side — the
        quantity the rolling filter bounds."""
        return self.filter.buf_rows if self.rolling else self._tri_rows

    def quality_summary(self) -> dict:
        """Ingest reconciliation + in-dispatch guard counters (ISSUE 4),
        assembled on the shared telemetry aggregation path (key set is
        the stable contract; the pooled detector sums these per-station
        dicts through the same ``merge_counts``)."""
        return tele_mod.quality_view(self.ring.quality, self.qc)

    # -- ingestion -----------------------------------------------------------

    def push(self, chunk: np.ndarray, offset: int | None = None) -> int:
        """Ingest one chunk (optionally placed at an absolute sample
        ``offset`` — late/overlapping/gapped arrivals are reconciled by
        the ring); returns pairs emitted by its ready blocks."""
        assert not self.external, \
            "pooled stations are pushed through their StreamingDetector"
        self.telemetry.start()
        with self.telemetry.tracer.span("chunk",
                                        station=self._pool_idx) as sp:
            emitted = self._push(chunk, offset)
        self._record_chunk(int(np.asarray(chunk).size), sp.dur_s)
        return emitted

    def _push(self, chunk: np.ndarray, offset: int | None) -> int:
        """The children of one push's ``chunk`` span: ``ingest`` (ring
        framing and the duplicate guard), then each ready block through
        the step."""
        with self.telemetry.tracer.span("ingest", station=self._pool_idx):
            ready = self._dedup(self.ring.push(chunk, offset))
        return sum(self._ingest_block(*b) for b in ready)

    def _record_chunk(self, n_samples: int, wall: float) -> None:
        self.stats.chunks += 1
        self.stats.samples += n_samples
        self.stats.record_wall(wall)
        self.telemetry.record_chunk(self._pool_idx, wall, n_samples)

    def _dedup(self, ready: list, tail: bool = False) -> list:
        """``ready`` (base_id, block, mask) blocks with the duplicate
        guard's flags merged into their masks, under the ``dedup`` span;
        ``tail`` marks a flush tail, which ends at ``ring.next_fp``."""
        end_id = self.ring.next_fp if tail else None
        with self.telemetry.tracer.span("dedup",
                                        station=self._pool_idx) as sp:
            before = self.qc["duplicate_fingerprints"]
            out = [(b, block, self._flag_duplicates(b, block, mask, end_id))
                   for b, block, mask in ready]
            sp.set(flagged=self.qc["duplicate_fingerprints"] - before)
        return out

    def _flag_duplicates(self, base_id: int, block: np.ndarray,
                         mask: np.ndarray | None,
                         end_id: int | None = None) -> np.ndarray | None:
        """Sample-exact repeated-segment detector (ISSUE 4, host side).

        Hashes every (still-valid) fingerprint's raw sample window and
        flags exact repeats of any window seen within the last
        ``dup_window_fingerprints`` ids — telemetry-duplicated blocks and
        flat-lined channels produce *bit-exact* windows, repeating
        earthquakes never do (independent noise floors), so the guard
        cannot touch clean data. Flagged fingerprints merge into the
        block's validity mask: suppressed in-dispatch, never inserted.
        ``end_id`` is one past the last fingerprint this block consumes
        from the id space (a flush tail consumes fewer than a whole
        block; defaulting to a full block there would purge the hash
        history early and leak copies whose original sits at the
        horizon's edge).
        """
        if self.dup_window <= 0:
            return mask
        fcfg = self.cfg.fingerprint
        w, lag = fcfg.window_samples, fcfg.lag_samples
        n = self.scfg.block_fingerprints
        valid = (np.ones(n, bool) if mask is None
                 else np.asarray(mask, bool).copy())
        flagged = 0
        block = np.ascontiguousarray(block, np.float32)
        # fingerprint windows overlap by w - lag, so hashing each whole
        # window would re-hash every byte ~w/lag times. Instead each
        # lag-aligned stride is digested once and a fingerprint's hash
        # combines its k full-stride digests plus the sub-stride tail —
        # still exactly window-equality (up to hash collision), at ~1x
        # the input bytes.
        k, tail = w // lag, w % lag
        strides: list[bytes | None] = [None] * (n + k)

        def stride(s: int) -> bytes:
            if strides[s] is None:
                strides[s] = hashlib.blake2b(
                    block[s * lag: (s + 1) * lag].tobytes(),
                    digest_size=8).digest()
            return strides[s]

        for i in range(n):
            if not valid[i]:
                continue
            fid = base_id + i
            parts = b"".join(stride(i + j) for j in range(k))
            if tail:
                parts += block[(i + k) * lag: (i + k) * lag + tail].tobytes()
            h = int.from_bytes(
                hashlib.blake2b(parts, digest_size=8).digest(), "little")
            if h in self._dup_map:
                valid[i] = False
                flagged += 1
            else:
                self._dup_map[h] = fid
                self._dup_hist.append((fid, h))
        floor = (base_id + n if end_id is None else end_id) \
            - self.dup_window
        while self._dup_hist and self._dup_hist[0][0] < floor:
            old_id, old_h = self._dup_hist.popleft()
            if self._dup_map.get(old_h) == old_id:
                del self._dup_map[old_h]
        if flagged:
            self.qc["duplicate_fingerprints"] += flagged
            return valid
        return mask

    def _ingest_block(self, base_id: int, block: np.ndarray,
                      mask: np.ndarray | None = None) -> int:
        """One block whose duplicates ``_dedup`` has flagged: into the
        warm-up reservoir, or through the step."""
        if not self.stats_frozen:
            coeffs = block_coeffs(jnp.asarray(block), self.cfg.fingerprint)
            rows = np.asarray(coeffs)
            # gap-masked fingerprints hold sentinel samples — keep their
            # rows out of the §5.2 statistics reservoir
            self.mad.update(rows if mask is None else rows[mask])
            # the fused drain recomputes coefficients inside its single
            # dispatch — retaining them here (O(warmup), O(trace) in the
            # deferred-freeze mode) would be dead weight; the unfused
            # drain replays the exact buffered coefficients
            self.pending.append((base_id, np.asarray(block, np.float32),
                                 None if self.fused else coeffs, mask))
            warm = self.scfg.stats_warmup_blocks
            if warm > 0 and len(self.pending) >= warm:
                self._freeze_stats()
                return self._drain_pending()
            return 0
        return self._process(base_id, block=block, valid=mask, primed=True)

    def _freeze_stats(self) -> None:
        med, mad = self.mad.stats()
        self._set_frozen(med, mad)

    def _drain_pending(self) -> int:
        emitted = 0
        for base_id, block, coeffs, mask in self.pending:
            emitted += self._process(base_id, block=block, coeffs=coeffs,
                                     valid=mask, primed=True)
        self.pending = []
        return emitted

    def _absorb_qc(self, qc: np.ndarray, n_masked: int) -> None:
        qc = np.asarray(qc).reshape(-1)
        self.qc["duplicate_fingerprints"] += int(qc[0])
        self.qc["saturated_lookups"] += int(qc[1])
        self.qc["limited_pairs"] += int(qc[2])
        # n_masked covers host-side suppression (gap overlap + sample-
        # exact dup flags); qc[0] adds the in-dispatch dup_sig_tables
        # suppressions so the superset invariant holds either way
        self.qc["suppressed_fingerprints"] += int(n_masked) + int(qc[0])
        # the telemetry tail of the vector (pairs emitted, device-masked
        # fingerprints, collision counts) mirrors into registry counters
        self.telemetry.record_step(self._pool_idx, qc)

    def _process(self, base_id: int, *, block: np.ndarray | None = None,
                 coeffs: jax.Array | None = None,
                 valid: np.ndarray | None = None,
                 primed: bool = False, n_adv: int | None = None) -> int:
        """One block through the device step (fused or legacy chain).

        ``valid`` masks fingerprints suppressed in-dispatch (gap overlap
        or a zero-padded flush tail). ``primed`` says the block is fully
        framed — its tail correctly primes the device halo even when some
        fingerprints are masked (gap blocks), unlike a padded tail.
        ``n_adv`` is the id-space advance (defaults to a whole block; a
        flush tail advances only by its consumed fingerprints).
        """
        fcfg, lcfg = self.cfg.fingerprint, self.cfg.lsh
        window = self.scfg.window_fingerprints
        sat = self.scfg.saturation_limit
        dup = self.scfg.dup_sig_tables
        occ = self.scfg.occ_limit
        ctr = 1 if self.scfg.telemetry else 0
        mp = self.scfg.max_pairs_per_block
        ver = self.scfg.verify_code
        mj = self.scfg.verify_min_jaccard
        n = self.scfg.block_fingerprints
        if n_adv is None:
            n_adv = n
        st = self._pool_idx
        advance = self.fused and valid is None and self._halo_ok
        with self.telemetry.tracer.span("ingest", station=st):
            vmask = (np.ones(n, bool) if valid is None
                     else np.asarray(valid, bool))
            if advance:
                host = (np.asarray(block, np.float32)[-self.ring.advance:],)
            elif self.fused or coeffs is None:
                host = (block, vmask)
            else:
                host = (vmask,)
        with self.telemetry.tracer.span("fused_step", station=st) as step:
            with self.telemetry.tracer.span("put", station=st) as sp:
                dev = [jnp.asarray(x) for x in host]
                bid = jnp.int32(base_id)
                sp.set(bytes=sum(x.nbytes for x in host))
            with self.telemetry.tracer.span("dispatch", station=st):
                if advance:
                    self.fstate, pairs, qc = fused_mod.step_advance(
                        self.fstate, dev[0], self.mappings, bid, fcfg, lcfg,
                        window, sat, dup, occ, ctr, mp, ver, mj)
                elif self.fused:
                    self.fstate, pairs, qc = fused_mod.step_block(
                        self.fstate, dev[0], self.mappings, bid, dev[1],
                        fcfg, lcfg, window, sat, dup, occ, ctr, mp, ver, mj)
                    # a zero-padded tail leaves the device halo dirty and
                    # the next block must re-seed through step_block; a
                    # fully framed (gap-masked) block primes it clean
                    self._halo_ok = valid is None or primed
                else:
                    if coeffs is None:
                        coeffs = block_coeffs(dev[0], fcfg)
                    med, mad = self._med_mad
                    self._state, pairs, qc = stream_step(
                        self._state, coeffs, med, mad, self.mappings, bid,
                        dev[-1], fcfg, lcfg, window, sat, dup, occ, ctr, mp,
                        ver, mj)
            # one device_get over the whole step output (ISSUE 8: a
            # single transfer, not four), after the wait for the device;
            # with compaction on, the pulled pair arrays are
            # O(max_pairs), not O(t·N·cap)
            out = ((pairs.idx1, pairs.idx2, pairs.sim, pairs.valid), qc)
            with self.telemetry.tracer.span("wait", station=st):
                jax.block_until_ready(out)
            with self.telemetry.tracer.span("pull", station=st) as sp:
                pairs_np, qc = jax.device_get(out)
                sp.set(bytes=sum(x.nbytes for x in pairs_np) + qc.nbytes)
        self.telemetry.record_fused_wall(str(st), step.dur_s)
        self._absorb_qc(qc, n_adv - int(vmask[:n_adv].sum()))
        with self.telemetry.tracer.span("host_tail", station=st) as tail:
            m = self._consume(base_id, n_adv, int(vmask.sum()), pairs_np)
            tail.set(pairs=m)
        self.telemetry.record_host_tail(st, tail.dur_s)
        return m

    def _consume(self, base_id: int, n_adv: int, n_valid: int,
                 pairs_np: tuple[np.ndarray, ...]) -> int:
        """Host-side tail of a step: triplet accounting + rolling filter.

        Shared by the solo path and the pooled detector (which hands each
        station its slice of the vmapped step output). ``n_adv`` advances
        the processed-id frontier (full id-space coverage of the block,
        gaps included); ``n_valid`` counts the real fingerprints.
        """
        i1, i2, sim, pv = pairs_np
        m = int(pv.sum())
        self.processed_fp = base_id + n_adv
        if m:
            tri = np.stack([i1[pv], i2[pv], sim[pv]], axis=1).astype(np.int64)
            if self.rolling:
                self.filter.add(tri)
            else:
                self.triplets.append(tri)
                self._tri_rows += m
        if self.rolling:
            self.filter.advance(self.processed_fp)
            self.peak_tri_rows = max(self.peak_tri_rows,
                                     self.filter.peak_rows)
        else:
            self.peak_tri_rows = max(self.peak_tri_rows, self._tri_rows)
        self.stats.blocks += 1
        self.stats.fingerprints += n_valid
        self.stats.pairs += m
        return m

    def flush(self) -> int:
        """Process the buffered tail: freeze stats if still warming up,
        drain pending blocks, and run the partial last block (masked).

        With ``stats_warmup_blocks == 0`` this is where the freeze always
        happens: the reservoir has absorbed the whole stream, so the
        buffered warmup fingerprints are binarized with the matured
        statistics (the re-binarize-after-freeze hook).
        """
        if self.external:
            return 0                # the owning detector flushes the pool
        emitted = 0
        with self.telemetry.tracer.span("ingest", station=self._pool_idx):
            blocks = self._dedup(self.ring.flush_ready())
            part = self.ring.flush_partial()
            if part is not None:
                (part,) = self._dedup([part], tail=True)
        ready = sum(self._ingest_block(*b) for b in blocks)
        part_coeffs = None
        if part is not None:
            base_id, block, mask = part
            if not self.stats_frozen or not self.fused:
                part_coeffs = block_coeffs(jnp.asarray(block),
                                           self.cfg.fingerprint)
            if not self.stats_frozen:
                self.mad.update(np.asarray(part_coeffs)[mask])
        if not self.stats_frozen:
            if self.mad.filled < 2:
                return ready  # not enough signal ever arrived
            self._freeze_stats()
            emitted += self._drain_pending()
        emitted += ready
        if part is not None:
            base_id, block, mask = part
            emitted += self._process(base_id, block=block,
                                     coeffs=part_coeffs, valid=mask,
                                     n_adv=self.ring.next_fp - base_id)
        return emitted

    def accumulated_pairs(self, pad_to: int = 1024) -> Pairs:
        """All emitted triplets as a masked fixed-size ``Pairs``."""
        tri = (np.concatenate(self.triplets, axis=0) if self.triplets
               else np.zeros((0, 3), np.int64))
        return pairs_from_triplets(tri, pad_to)

    def finalize(self) -> tuple[Events, Pairs, dict]:
        """Occurrence filter + channel merge + diagonal clustering.

        Parity mode runs the offline reduction over the full accumulated
        pair set. Bounded mode closes the open rolling window and returns
        the concatenation of per-window events (boundary-merged); raw
        pairs were already retired window-by-window, so the returned
        ``Pairs`` is empty.
        """
        self.flush()
        lcfg, acfg = self.cfg.lsh, self.cfg.align
        n_fp = self.ring.next_fp
        if self.rolling:
            self.filter.close_all(self.processed_fp)
            events = events_from_rows(self.filter.all_rows())
            fstats = {
                "fingerprints": n_fp,
                "pairs": self.filter.pairs_kept,
                "windows": self.filter.windows_closed,
                "events": int(events.count()),
                "peak_buffered_triplets": self.peak_tri_rows,
                "quality": self.quality_summary(),
            }
            return events, pairs_from_triplets(np.zeros((0, 3))), fstats
        pairs = self.accumulated_pairs()
        fstats = {"fingerprints": n_fp, "quality": self.quality_summary()}
        if lcfg.occurrence_frac > 0 and n_fp > 0:
            pairs, excluded = host_occurrence_filter(pairs, n_fp, lcfg)
            fstats["excluded_fingerprints"] = int(excluded.sum())
        merged = align_mod.merge_channels(
            [(pairs.dt, pairs.idx1, pairs.sim, pairs.valid)],
            acfg.channel_threshold)
        events = align_mod.cluster_station(merged, acfg)
        fstats["pairs"] = int(pairs.count())
        fstats["events"] = int(events.count())
        fstats["peak_buffered_triplets"] = self.peak_tri_rows
        return events, pairs, fstats

    # -- snapshot / restore -------------------------------------------------

    def snapshot_state(self) -> tuple[dict, dict]:
        """(flat arrays, json-able extra) capturing this station exactly."""
        state = self.state
        arrays = {
            "index/sig": np.asarray(jax.device_get(state.sig)),
            "index/ids": np.asarray(jax.device_get(state.ids)),
            "index/cursor": np.asarray(jax.device_get(state.cursor)),
            "index/inserted": np.asarray(jax.device_get(state.inserted)),
            "index/traffic": np.asarray(jax.device_get(state.traffic)),
            "index/occ": np.asarray(jax.device_get(state.occ)),
            "index/epoch": np.asarray(jax.device_get(state.epoch)),
            "index/pk": np.asarray(jax.device_get(state.pk)),
        }
        ring_a, ring_s = self.ring.snapshot()
        arrays["ring/buf"] = ring_a["buf"]
        arrays["ring/vbuf"] = ring_a["vbuf"]
        mad_a, mad_s = self.mad.snapshot()
        arrays["mad/rows"] = mad_a["rows"]
        arrays["stats/chunk_wall_s"] = np.asarray(self.stats.chunk_wall_s,
                                                  np.float64)
        extra = {
            "ring": ring_s, "mad": mad_s,
            "frozen": self.stats_frozen,
            "processed_fp": self.processed_fp,
            "peak_tri_rows": self.peak_tri_rows,
            "qc": dict(self.qc),
            "stats": {"chunks": self.stats.chunks,
                      "blocks": self.stats.blocks,
                      "samples": self.stats.samples,
                      "fingerprints": self.stats.fingerprints,
                      "pairs": self.stats.pairs,
                      "wall_total_s": self.stats.wall_total_s},
        }
        if self.stats_frozen:
            arrays["med"] = np.asarray(self._med_mad[0])
            arrays["mad_stat"] = np.asarray(self._med_mad[1])
        if self.dup_window > 0:
            arrays["dup/ids"] = np.asarray(
                [i for i, _ in self._dup_hist], np.int64)
            arrays["dup/hash"] = np.asarray(
                [h for _, h in self._dup_hist], np.uint64)
        if self.pending:
            n = self.scfg.block_fingerprints
            arrays["pending/base"] = np.asarray(
                [b for b, _, _, _ in self.pending], np.int64)
            arrays["pending/blocks"] = np.stack(
                [b for _, b, _, _ in self.pending]).astype(np.float32)
            # gap masks; an all-True row restores to None (clean block)
            arrays["pending/valid"] = np.stack(
                [np.ones(n, bool) if m is None else np.asarray(m, bool)
                 for _, _, _, m in self.pending])
            if not self.fused:      # unfused drains replay exact coeffs
                arrays["pending/coeffs"] = np.stack(
                    [np.asarray(c) for _, _, c, _ in self.pending]) \
                    .astype(np.float32)
        if self.rolling:
            f_a, f_s = self.filter.snapshot()
            arrays["filter/buf"] = f_a["buf"]
            arrays["filter/events"] = f_a["events"]
            extra["filter"] = f_s
        else:
            arrays["triplets"] = (
                np.concatenate(self.triplets, axis=0).astype(np.int64)
                if self.triplets else np.zeros((0, 3), np.int64))
        return arrays, extra

    def restore_state(self, arrays: dict, extra: dict) -> None:
        init = index_mod.init_index(self.cfg.lsh, self.icfg)
        restored = IndexState(
            sig=jnp.asarray(arrays["index/sig"], jnp.uint32),
            ids=jnp.asarray(arrays["index/ids"], jnp.int32),
            cursor=jnp.asarray(arrays["index/cursor"], jnp.int32),
            inserted=jnp.asarray(arrays["index/inserted"], jnp.int32),
            # pre-limiter snapshots lack the guard counters: the cursor
            # restores the lifetime traffic those snapshots ran under,
            # and the epoch is re-derived from the processed frontier —
            # an epoch of 0 would make the first windowed expire
            # right-shift the counter by the whole elapsed epoch span
            # and release every quarantined bucket at once
            traffic=jnp.asarray(arrays.get("index/traffic",
                                           arrays["index/cursor"]),
                                jnp.int32),
            occ=jnp.asarray(arrays["index/occ"], jnp.int32)
            if "index/occ" in arrays else init.occ,
            epoch=jnp.asarray(arrays["index/epoch"], jnp.int32)
            if "index/epoch" in arrays else jnp.asarray(
                max(0, int(extra["processed_fp"])
                    - self.scfg.window_fingerprints)
                // max(self.scfg.window_fingerprints, 1), jnp.int32),
            # pre-verify snapshots lack the packed-fingerprint ring; an
            # empty ring only costs already-inserted ids their exact
            # Jaccard (scored 0) until the window rolls over
            pk=jnp.asarray(arrays["index/pk"], jnp.uint32)
            if "index/pk" in arrays else init.pk)
        assert restored.shape == init.shape, (restored.shape, init.shape)
        assert restored.occ.shape == init.occ.shape, \
            (restored.occ.shape, init.occ.shape)
        assert restored.pk.shape == init.pk.shape, \
            (restored.pk.shape, init.pk.shape)
        self._state = restored
        self.fstate = None
        self._halo_ok = False
        ring_a = {"buf": arrays["ring/buf"]}
        if "ring/vbuf" in arrays:
            ring_a["vbuf"] = arrays["ring/vbuf"]
        self.ring.restore(ring_a, extra["ring"])
        self.mad.restore({"rows": arrays["mad/rows"]}, extra["mad"])
        self.qc.update(extra.get("qc", {}))
        self._dup_hist.clear()
        self._dup_map = {}
        if "dup/ids" in arrays:
            ids = np.asarray(arrays["dup/ids"], np.int64)
            hashes = np.asarray(arrays["dup/hash"], np.uint64)
            for i in range(ids.shape[0]):
                fid, h = int(ids[i]), int(hashes[i])
                self._dup_hist.append((fid, h))
                self._dup_map[h] = fid
        self._med_mad = None
        if extra["frozen"]:
            self._set_frozen(arrays["med"], arrays["mad_stat"])
        self.pending = []
        if "pending/base" in arrays:
            bases = np.asarray(arrays["pending/base"], np.int64)
            blocks = np.asarray(arrays["pending/blocks"], np.float32)
            coeffs = (np.asarray(arrays["pending/coeffs"], np.float32)
                      if "pending/coeffs" in arrays else None)
            masks = (np.asarray(arrays["pending/valid"], bool)
                     if "pending/valid" in arrays else None)

            def _mask(i):
                if masks is None or masks[i].all():
                    return None
                return masks[i]

            self.pending = [
                (int(bases[i]), blocks[i],
                 None if coeffs is None else jnp.asarray(coeffs[i]),
                 _mask(i))
                for i in range(bases.shape[0])]
        if self.rolling:
            self.filter.restore(
                {"buf": arrays["filter/buf"],
                 "events": arrays["filter/events"]}, extra["filter"])
            self.triplets = []
            self._tri_rows = 0
        else:
            tri = np.asarray(arrays["triplets"], np.int64).reshape(-1, 3)
            self.triplets = [tri] if tri.shape[0] else []
            self._tri_rows = int(tri.shape[0])
        self.processed_fp = int(extra["processed_fp"])
        self.peak_tri_rows = int(extra["peak_tri_rows"])
        s = extra["stats"]
        wall = np.asarray(arrays["stats/chunk_wall_s"], np.float64)
        self.stats = StreamStats(
            chunks=int(s["chunks"]), blocks=int(s["blocks"]),
            samples=int(s["samples"]),
            fingerprints=int(s["fingerprints"]), pairs=int(s["pairs"]),
            # pre-ISSUE-6 snapshots carry the full per-chunk list and no
            # running total: their window-truncated restore keeps the
            # exact total via the stored sum
            wall_total_s=float(s.get("wall_total_s", wall.sum())),
            chunk_wall_s=collections.deque(wall.tolist(),
                                           maxlen=WALL_WINDOW))


def _per_station_stats(med_mad, n_stations: int) -> list:
    """Frozen statistics as one (med, mad) pair per station: ``med_mad``
    is a shared pair of (n_coeff,) arrays, or (S, n_coeff) arrays with one
    row per station (``core.detect.station_stats``), or None."""
    if med_mad is None:
        return [None] * n_stations
    med, mad = med_mad
    if np.ndim(med) == 1:
        return [(med, mad)] * n_stations
    if len(med) != n_stations or len(mad) != n_stations:
        raise ValueError(f"per-station med_mad needs {n_stations} rows, "
                         f"got {len(med)} and {len(mad)}")
    return [(med[i], mad[i]) for i in range(n_stations)]


class StreamingDetector:
    """Multi-station streaming FAST: push chunks, read detections.

    ``push`` accepts (n_stations, chunk_len) or a 1-D chunk for a single
    station; chunk lengths may vary call to call. ``finalize`` runs the
    per-station alignment and (when n_stations ≥ 2) the network
    association, mirroring ``detect_events``. In bounded mode each push
    also polls the incremental association: newly final multi-station
    detections land in ``alerts`` as they close, not only at finalize.

    With ``StreamConfig.pooled`` (the default) and ≥2 stations, the
    per-station device states are stacked into one pool and every ready
    block steps all stations through a single vmapped fused dispatch —
    the per-station ``StationStream`` objects keep only host-side state
    (ring framing, reservoir, rolling filter, stats).
    """

    def __init__(self, cfg: DetectConfig, scfg: StreamConfig | None = None,
                 n_stations: int = 1,
                 med_mad: tuple[np.ndarray, np.ndarray] | None = None,
                 station_xy: np.ndarray | None = None):
        self.cfg = cfg
        self.scfg = scfg or StreamConfig()
        self.station_xy = (np.asarray(station_xy, np.float32)
                           if station_xy is not None else None)
        if self.station_xy is not None \
                and self.station_xy.shape != (n_stations, 2):
            raise ValueError(f"station_xy must be ({n_stations}, 2) km, "
                             f"got {self.station_xy.shape}")
        # location/magnitude tier: active when a LocateConfig and station
        # geometry are both in hand (and there is a network to associate)
        self.locating = (cfg.locate is not None
                         and self.station_xy is not None
                         and n_stations >= 2)
        self.pooled = (self.scfg.fused and self.scfg.pooled
                       and n_stations >= 2)
        # sharded station pool (ISSUE 10): the capability probe returns a
        # 1-axis ``stations`` mesh when >1 device is visible, else None —
        # the None keeps every pool dispatch on the single-device vmap
        # path. The pool is padded up to a multiple of the mesh width
        # with throwaway station rows (row-independent math; their output
        # is never read) so the leading axis always divides the mesh.
        self.mesh = (dist.station_mesh(n_stations)
                     if self.pooled and self.scfg.sharded else None)
        self.pool_pad = dist.padded_pool_width(n_stations,
                                               self.mesh) - n_stations
        self.telemetry = StreamTelemetry(n_stations)
        self.stations = [StationStream(cfg, self.scfg, med_mad=mm,
                                       external=self.pooled,
                                       telemetry=self.telemetry)
                         for mm in _per_station_stats(med_mad, n_stations)]
        self.pstate: fused_mod.FusedState | None = None
        self._halo_ok = False
        self.mappings = self.stations[0].mappings
        for i, st in enumerate(self.stations):
            st._owner, st._pool_idx = self, i
        if self.pooled and med_mad is not None:
            self._build_pool()
        self.rolling = self.scfg.filter_window_fingerprints > 0
        self.alerts: list[np.ndarray] = []   # (k, ALERT_COLS) rows
        # alerted keys + the best station multiplicity each has alerted
        # at: (dt, onset, best_n_stations). A group whose multiplicity
        # later grows past its recorded best re-emits as an upgrade.
        self._emitted = np.zeros((0, 3), np.int64)
        self._assoc_lo = 0
        # bounded amplitude timeline (magnitude source): per station,
        # lag-bin → peak |sample| seen for that bin, max-merged across
        # (possibly late / duplicated) arrivals and pruned with the
        # association floor. Approximate by design — amplitudes are read
        # at fingerprint-lag resolution, which is what the relative-
        # magnitude ratio needs.
        self._amp: list[dict[int, float]] = [{} for _ in range(n_stations)]
        self._polled_windows = 0  # window closes seen by the last poll
        # monotonic corpus version: bumps whenever ingestion may have
        # changed the index pool, so a serving engine can gate its
        # pool_serving_state() refreshes on "did anything arrive?"
        self.serving_version = 0

    def push(self, chunk: np.ndarray, offset: int | None = None) -> int:
        """Ingest one network chunk; ``offset`` places it at an absolute
        sample offset on every station's timeline (late / duplicated /
        gapped telemetry is reconciled per station by the rings; chunks
        are network-aligned, so one offset serves all stations — a
        single-station outage is NaN samples inside the chunk)."""
        chunk = np.asarray(chunk, np.float32)
        if chunk.ndim == 1:
            chunk = chunk[None, :]
        assert chunk.shape[0] == len(self.stations), \
            (chunk.shape, len(self.stations))
        self.telemetry.start()
        with self.telemetry.tracer.span(
                "chunk", stations=len(self.stations)) as sp:
            if self.locating:
                pos = (self.stations[0].ring.frontier if offset is None
                       else int(offset))
                for i in range(chunk.shape[0]):
                    self._note_amps(i, pos, chunk[i])
            if self.pooled:
                emitted = self._pool_push(chunk, offset)
            else:
                emitted = sum(st._push(chunk[i], offset)
                              for i, st in enumerate(self.stations))
            if self.rolling and len(self.stations) >= 2:
                with self.telemetry.tracer.span("detections"):
                    new = self.poll_detections()
                if new.shape[0]:
                    self.alerts.append(new)
        # the stations share the push, so each records its wall
        for i, st in enumerate(self.stations):
            st._record_chunk(int(chunk[i].size), sp.dur_s)
        self.serving_version += 1
        return emitted

    # -- pooled stepping ----------------------------------------------------

    def _build_pool(self) -> None:
        """Stack the stations' device state into one vmappable pool.

        With a mesh in hand the stacked pytree is padded to a multiple
        of the mesh width (throwaway station rows cloned from fresh
        index state + station 0's statistics) and every leaf is placed
        with ``NamedSharding(mesh, P('stations'))`` — per-shard
        ``device_put``, so the donated steady state never pays a cross-
        device reshard."""
        states = [st._state for st in self.stations]
        meds = [st._med_mad[0] for st in self.stations]
        mads = [st._med_mad[1] for st in self.stations]
        if self.pool_pad:
            states += [index_mod.init_index(self.cfg.lsh,
                                            self.stations[0].icfg)
                       for _ in range(self.pool_pad)]
            meds += [meds[0]] * self.pool_pad
            mads += [mads[0]] * self.pool_pad
        pstate = fused_mod.init_pool_state(
            states, self.cfg.fingerprint.halo_samples, meds, mads)
        if self.mesh is not None:
            pstate = jax.device_put(pstate,
                                    dist.pool_sharding(self.mesh))
            # replicate the hash mappings across the mesh once: passing
            # the device-0-committed copy would re-broadcast it on every
            # dispatch
            self._pool_mappings = jax.device_put(
                self.mappings, dist.replicated_sharding(self.mesh))
        else:
            self._pool_mappings = self.mappings
        self.pstate = pstate
        for st in self.stations:
            st._state = None        # the pool owns the buffers now
        self._halo_ok = False

    def _pad_rows(self, x: np.ndarray, fill=0) -> np.ndarray:
        """Append the pool's pad-station rows to a host-side (S, ...)
        input (zero samples / all-invalid masks — the pad rows' output
        is never read, this just keeps the shapes mesh-divisible)."""
        if not self.pool_pad:
            return x
        pad = np.full((self.pool_pad,) + x.shape[1:], fill, x.dtype)
        return np.concatenate([x, pad])

    def _pool_push(self, chunk: np.ndarray, offset: int | None = None
                   ) -> int:
        with self.telemetry.tracer.span("ingest", station="pool"):
            ready = self._pool_dedup(self._pool_blocks(
                [st.ring.push(chunk[i], offset)
                 for i, st in enumerate(self.stations)]))
        return sum(self._pool_ingest_block(*b) for b in ready)

    def _pool_blocks(self, per_st: list) -> list:
        """The lockstep rings' ready blocks, each as (base_id, (S, block)
        stack, per-station masks)."""
        s = len(self.stations)
        return [(per_st[0][k][0], np.stack([per_st[i][k][1]
                                            for i in range(s)]),
                 [per_st[i][k][2] for i in range(s)])
                for k in range(len(per_st[0]))]

    def _pool_dedup(self, ready: list, tail: bool = False) -> list:
        """``StationStream._dedup`` over every station of each pooled
        block, under one ``dedup`` span."""
        with self.telemetry.tracer.span("dedup", station="pool") as sp:
            before = sum(st.qc["duplicate_fingerprints"]
                         for st in self.stations)
            out = [(b, blocks, [st._flag_duplicates(
                        b, blocks[i], masks[i],
                        st.ring.next_fp if tail else None)
                        for i, st in enumerate(self.stations)])
                   for b, blocks, masks in ready]
            sp.set(flagged=sum(st.qc["duplicate_fingerprints"]
                               for st in self.stations) - before)
        return out

    def _pool_ingest_block(self, base_id: int, blocks: np.ndarray,
                           masks: list) -> int:
        """One pooled block whose duplicates ``_pool_dedup`` has
        flagged: into the warm-up reservoirs, or through the step."""
        if self.pstate is None:
            coeffs = np.asarray(pool_block_coeffs(jnp.asarray(blocks),
                                                  self.cfg.fingerprint))
            for i, st in enumerate(self.stations):
                st.mad.update(coeffs[i] if masks[i] is None
                              else coeffs[i][masks[i]])
                st.pending.append((base_id, blocks[i], None, masks[i]))
            warm = self.scfg.stats_warmup_blocks
            if warm > 0 and len(self.stations[0].pending) >= warm:
                self._freeze_pool()
                return self._drain_pool()
            return 0
        return self._pool_process(base_id, blocks, masks=masks)

    def _freeze_pool(self) -> None:
        for st in self.stations:
            if not st.stats_frozen:
                st._freeze_stats()  # external: records stats only
        self._build_pool()

    def _drain_pool(self) -> int:
        emitted = 0
        pend = [st.pending for st in self.stations]
        for k in range(len(pend[0])):
            base_id = pend[0][k][0]
            blocks = np.stack([pend[i][k][1]
                               for i in range(len(self.stations))])
            masks = [pend[i][k][3] for i in range(len(self.stations))]
            emitted += self._pool_process(base_id, blocks, masks=masks)
        for st in self.stations:
            st.pending = []
        return emitted

    def _pool_process(self, base_id: int, blocks: np.ndarray,
                      masks: list | None = None, primed: bool = True,
                      n_adv: int | None = None) -> int:
        """One lockstep block through the vmapped pool step.

        ``masks``: per-station gap masks (None entries = clean); a flush
        tail passes the shared tail mask per station with
        ``primed=False`` and the consumed id advance ``n_adv``.
        """
        fcfg, lcfg = self.cfg.fingerprint, self.cfg.lsh
        window = self.scfg.window_fingerprints
        sat = self.scfg.saturation_limit
        dup = self.scfg.dup_sig_tables
        occ = self.scfg.occ_limit
        ctr = 1 if self.scfg.telemetry else 0
        mp = self.scfg.max_pairs_per_block
        ver = self.scfg.verify_code
        mj = self.scfg.verify_min_jaccard
        n = self.scfg.block_fingerprints
        s = len(self.stations)
        clean = masks is None or all(m is None for m in masks)
        if n_adv is None:
            n_adv = n
        advance = clean and self._halo_ok and n_adv == n
        with self.telemetry.tracer.span("ingest", station="pool"):
            if advance:
                vm = np.ones((s, n), bool)
                host = (self._pad_rows(
                    blocks[:, -self.stations[0].ring.advance:]),)
            else:
                vm = np.stack([
                    np.ones(n, bool) if (masks is None or masks[i] is None)
                    else np.asarray(masks[i], bool) for i in range(s)])
                host = (self._pad_rows(blocks),
                        self._pad_rows(vm, fill=False))
        # per-station host inputs go straight to their shard: under a
        # mesh, a plain jnp.asarray would land the whole array on device
        # 0 and pay a second device-0 → shards scatter inside dispatch
        put = (jnp.asarray if self.mesh is None else
               functools.partial(jax.device_put,
                                 device=dist.pool_sharding(self.mesh)))
        with self.telemetry.tracer.span("fused_step", station="pool") as step:
            with self.telemetry.tracer.span("put", station="pool") as sp:
                dev = [put(x) for x in host]
                bid = jnp.int32(base_id)
                sp.set(bytes=sum(x.nbytes for x in host))
            with self.telemetry.tracer.span("dispatch", station="pool"):
                if advance:
                    self.pstate, pairs, qc = \
                        fused_mod.pool_step_advance_sharded(
                            self.pstate, dev[0], self._pool_mappings, bid,
                            fcfg, lcfg, window, sat, dup, occ, ctr, mp, ver,
                            mj, mesh=self.mesh)
                else:
                    self.pstate, pairs, qc = \
                        fused_mod.pool_step_block_sharded(
                            self.pstate, dev[0], self._pool_mappings, bid,
                            dev[1], fcfg, lcfg, window, sat, dup, occ, ctr,
                            mp, ver, mj, mesh=self.mesh)
                    self._halo_ok = clean or primed
            # one transfer for the whole pooled step output, after the
            # wait for the device
            out = ((pairs.idx1, pairs.idx2, pairs.sim, pairs.valid), qc)
            with self.telemetry.tracer.span("wait", station="pool"):
                jax.block_until_ready(out)
            with self.telemetry.tracer.span("pull", station="pool") as sp:
                (i1, i2, sim, pv), qc = jax.device_get(out)
                sp.set(bytes=i1.nbytes + i2.nbytes + sim.nbytes + pv.nbytes
                       + qc.nbytes)
        # one watchdog step per pooled dispatch (all stations share it)
        self.telemetry.record_fused_wall("pool", step.dur_s)
        emitted = 0
        with self.telemetry.tracer.span("host_tail", station="pool") as tail:
            for i, st in enumerate(self.stations):
                st._absorb_qc(qc[i], n_adv - int(vm[i, :n_adv].sum()))
                emitted += st._consume(base_id, n_adv, int(vm[i].sum()),
                                       (i1[i], i2[i], sim[i], pv[i]))
            tail.set(pairs=emitted)
        self.telemetry.record_host_tail("pool", tail.dur_s)
        return emitted

    def _pool_flush(self) -> int:
        """Pool counterpart of ``StationStream.flush`` (lockstep rings ⇒
        every station tails at the same base id / consumed count)."""
        emitted = 0
        with self.telemetry.tracer.span("ingest", station="pool"):
            ready = self._pool_dedup(self._pool_blocks(
                [st.ring.flush_ready() for st in self.stations]))
            parts = [st.ring.flush_partial() for st in self.stations]
            part = None
            if parts[0] is not None:
                (part,) = self._pool_dedup(
                    self._pool_blocks([[p] for p in parts]), tail=True)
        ready = sum(self._pool_ingest_block(*b) for b in ready)
        if self.pstate is None:
            if part is not None:
                coeffs = np.asarray(pool_block_coeffs(
                    jnp.asarray(part[1]), self.cfg.fingerprint))
                for i, st in enumerate(self.stations):
                    st.mad.update(coeffs[i][part[2][i]])
            if any(st.mad.filled < 2 for st in self.stations):
                return ready
            self._freeze_pool()
            emitted += self._drain_pool()
        emitted += ready
        if part is not None:
            base_id, blocks, masks = part
            n_adv = self.stations[0].ring.next_fp - base_id
            emitted += self._pool_process(base_id, blocks, masks=masks,
                                          primed=False, n_adv=n_adv)
        return emitted

    def flush(self) -> int:
        """Process buffered tails on every station (pool-aware)."""
        self.serving_version += 1
        if self.pooled:
            return self._pool_flush()
        return sum(st.flush() for st in self.stations)

    def pool_serving_state(self) -> tuple[IndexState, jax.Array, jax.Array]:
        """(stacked index, med (S, C), mad (S, C)) for the serving loop —
        uniform whether the detector ran pooled or solo.

        Returns **copies**: the detector's own pool buffers are donated on
        every subsequent step, so handing out live references would let
        one more ``push`` delete the arrays a ``ServeDetectEngine`` is
        querying. The copy makes the serving state a stable read-only
        snapshot of the index at call time.
        """
        assert all(st.stats_frozen for st in self.stations)
        if self.pstate is not None:
            s = len(self.stations)
            # the slice also drops the mesh-pad rows of a sharded pool,
            # so serving always sees exactly the real stations
            return jax.tree.map(lambda x: jnp.array(x[:s]),
                                (self.pstate.index, self.pstate.med,
                                 self.pstate.mad))
        return (index_mod.stack_states([st.state for st in self.stations]),
                jnp.stack([st.med_mad[0] for st in self.stations]),
                jnp.stack([st.med_mad[1] for st in self.stations]))

    # -- elastic pool membership (ISSUE 10) ----------------------------------

    def _materialize_stations(self) -> None:
        """Pull each real station's index slice out of the (possibly
        sharded, possibly padded) pool back into per-station state —
        the first half of any pool re-pack. Pad rows are dropped here;
        they are re-cloned fresh by the next ``_build_pool``."""
        if self.pstate is None:
            return
        for st in self.stations:
            st._state = jax.tree.map(
                jnp.array,
                index_mod.slice_state(self.pstate.index, st._pool_idx))
        self.pstate = None

    def _repack_pool(self) -> None:
        """Re-probe the mesh for the current width, re-pad, re-shard and
        rebuild the stacked pool. The next block routes through the
        (already-traced-per-shape) ``pool_step_block`` seed path, so a
        width change costs one compile of the new-width executable and
        nothing else — donation and the ≤1-steady-state-trace invariant
        hold per pool width."""
        self.mesh = (dist.station_mesh(len(self.stations))
                     if self.scfg.sharded else None)
        self.pool_pad = dist.padded_pool_width(
            len(self.stations), self.mesh) - len(self.stations)
        self.telemetry.n_stations = len(self.stations)
        self._build_pool()

    def add_station(self, med_mad: tuple[np.ndarray, np.ndarray]
                    | None = None) -> int:
        """Elastically grow the live pool by one station; returns the new
        station's index.

        The stacked pytree is re-padded and re-sharded for the new width
        (``_repack_pool``). The joining station enters at the network
        frontier: its ring mirrors a peer's framing position with the
        whole pre-join span marked missing, so lockstep block emission
        (shared base ids) holds and the join span is suppressed
        in-dispatch rather than invented. ``med_mad`` defaults to station
        0's frozen statistics (network stations see similar noise floors;
        pass real statistics for production use). Serving engines built
        over the old width keep serving their snapshot — rebuild them to
        pick up the grown pool (``ServeDetectEngine`` pins its width).
        """
        if not self.pooled:
            raise ValueError(
                "add_station needs a pooled detector (StreamConfig.fused"
                " + pooled with ≥2 stations at construction)")
        if self.locating:
            raise ValueError(
                "add_station cannot extend the locate tier: station_xy "
                "geometry is fixed at construction — rebuild the "
                "detector with the new geometry instead")
        if self.pstate is None \
                or not all(st.stats_frozen for st in self.stations):
            raise ValueError(
                "add_station requires a live pool (statistics frozen and "
                "the stacked state built); push warmup chunks first")
        if med_mad is None:
            med_mad = tuple(np.asarray(m)
                            for m in self.stations[0].med_mad)
        self._materialize_stations()
        st = StationStream(self.cfg, self.scfg, med_mad=med_mad,
                           external=True, telemetry=self.telemetry)
        st._owner, st._pool_idx = self, len(self.stations)
        peer = self.stations[0]
        st.ring.start = peer.ring.start
        st.ring.next_fp = peer.ring.next_fp
        st.ring.buf = np.zeros(peer.ring.buf.size, np.float32)
        st.ring.vbuf = np.zeros(peer.ring.buf.size, bool)
        st.ring.quality["missing_samples"] += int(peer.ring.buf.size)
        st.processed_fp = peer.processed_fp
        if st.rolling and st.processed_fp:
            st.filter.advance(st.processed_fp)  # join cost paid up front
        self.stations.append(st)
        self._amp.append({})
        self._repack_pool()
        self.serving_version += 1
        return st._pool_idx

    def remove_station(self, station: int) -> None:
        """Elastically drop one station from the live pool (its index
        state and host buffers are discarded; remaining stations shift
        down, which renumbers pair/event station indices from here on).
        The pool is re-padded and re-sharded for the new width."""
        if not self.pooled or self.pstate is None:
            raise ValueError("remove_station requires a live pooled "
                             "detector (statistics frozen)")
        if self.locating:
            raise ValueError(
                "remove_station cannot shrink the locate tier: "
                "station_xy geometry is fixed at construction")
        if not 0 <= station < len(self.stations):
            raise IndexError(station)
        if len(self.stations) < 2:
            raise ValueError("cannot remove the last station")
        self._materialize_stations()
        dropped = self.stations.pop(station)
        dropped._owner = None
        dropped._state = None
        self._amp.pop(station)
        for i, st in enumerate(self.stations):
            st._pool_idx = i
        self._repack_pool()
        self.serving_version += 1

    # -- association / location / finalize ----------------------------------

    def _note_amps(self, st_i: int, pos: int, chunk: np.ndarray) -> None:
        """Max-merge a chunk's |samples| into station ``st_i``'s lag-bin
        amplitude timeline (idempotent under duplicate delivery; NaN
        telemetry contributes nothing)."""
        lag = self.cfg.fingerprint.lag_samples
        b0 = pos // lag
        lead = pos - b0 * lag
        x = np.full(lead + chunk.size, np.nan, np.float32)
        x[lead:] = chunk
        nb = -(-x.size // lag)
        x = np.concatenate([x, np.full(nb * lag - x.size, np.nan,
                                       np.float32)])
        a = np.abs(x).reshape(nb, lag)
        vals = np.where(np.isfinite(a), a, -1.0).max(axis=1)
        d = self._amp[st_i]
        for b, vv in enumerate(vals):
            if vv >= 0:
                key = b0 + b
                prev = d.get(key)
                if prev is None or vv > prev:
                    d[key] = float(vv)

    def _amp_fn(self, st_i: int, fp_index: int) -> float | None:
        """Peak |amplitude| over fingerprint ``fp_index``'s analysis
        window, from the bounded timeline (None when no bin survives)."""
        fcfg = self.cfg.fingerprint
        w_bins = max(1, -(-fcfg.window_samples // fcfg.lag_samples))
        d = self._amp[st_i]
        vals = [d[b] for b in range(fp_index, fp_index + w_bins) if b in d]
        return max(vals) if vals else None

    def _station_weights(self) -> np.ndarray:
        """Live per-station stack weights from the ingest/guard QC
        counters (``core.locate.station_weights``)."""
        return locate_mod.station_weights(
            [st.quality_summary() for st in self.stations],
            [st.stats.samples for st in self.stations],
            [st.ring.next_fp for st in self.stations], self.cfg.locate)

    def _locate_rows(self, rows: np.ndarray, onset_mat: np.ndarray,
                     score_mat: np.ndarray) -> tuple[np.ndarray, int]:
        """Location/magnitude columns for fresh alert rows; returns the
        (possibly moveout-filtered) rows and the rejected count."""
        lcfg = self.cfg.locate
        fcfg = self.cfg.fingerprint
        t0 = time.perf_counter()
        weights = self._station_weights()
        det = {"valid": np.ones(rows.shape[0], bool),
               "station_onset": onset_mat}
        loc = locate_mod.locate_detections(
            det, self.station_xy, weights, fcfg.lag_samples / fcfg.fs,
            lcfg)
        mags = locate_mod.magnitudes_from_onsets(
            onset_mat, rows[:, 0], det["valid"], self._amp_fn, weights,
            score_mat)
        ok = np.isfinite(loc["x_km"])
        rows[:, 5] = np.where(ok, np.round(
            np.nan_to_num(loc["x_km"]) * 1e3), locate_mod.LOC_NONE
            ).astype(np.int64)
        rows[:, 6] = np.where(ok, np.round(
            np.nan_to_num(loc["y_km"]) * 1e3), locate_mod.LOC_NONE
            ).astype(np.int64)
        mok = np.isfinite(mags)
        rows[:, 7] = np.where(mok, np.round(
            np.nan_to_num(mags) * 1e3), locate_mod.MAG_NONE
            ).astype(np.int64)
        rejected = 0
        if lcfg.reject_inconsistent:
            keep = np.asarray(loc["consistent"])
            rejected = int(rows.shape[0] - keep.sum())
            rows = rows[keep]
        self.telemetry.record_locate(
            groups=int(det["valid"].sum()),
            located=int(rows.shape[0]), rejected=rejected,
            wall=time.perf_counter() - t0)
        return rows, rejected

    def poll_detections(self) -> np.ndarray:
        """Incremental network association over closed-window events.

        Returns (k, ``ALERT_COLS``) int64 rows (dt, onset, n_stations,
        score, upgrade, x_mkm, y_mkm, mag_milli) for groups not alerted
        before, plus *upgrade* re-emissions — a previously alerted group
        whose station multiplicity has since grown re-emits with
        ``upgrade=1`` (and a refreshed location/magnitude). With the
        locate tier active, each fresh group is migration-located and
        sized; moveout-inconsistent groups are dropped (they may return
        later via the upgrade path if more stations join). ``finalize``
        remains the authoritative association over the full event history.
        """
        acfg = self.cfg.align
        if not self.rolling or len(self.stations) < 2:
            return np.zeros((0, ALERT_COLS), np.int64)
        # the active rows only change when a window closes — don't repeat
        # the association dispatch on pushes that closed nothing
        closed = sum(st.filter.windows_closed for st in self.stations)
        if closed == self._polled_windows:
            return np.zeros((0, ALERT_COLS), np.int64)
        self._polled_windows = closed
        per_station = [st.filter.rows_tail(self._assoc_lo)
                       for st in self.stations]
        if sum(r.shape[0] for r in per_station) == 0:
            return np.zeros((0, ALERT_COLS), np.int64)
        events = [events_from_rows(r) for r in per_station]
        det = align_mod.associate_network(events, acfg, len(self.stations),
                                          with_onsets=self.locating)
        v = np.asarray(det["valid"])
        rows = np.zeros((int(v.sum()), ALERT_COLS), np.int64)
        rows[:, 0] = np.asarray(det["dt"])[v]
        rows[:, 1] = np.asarray(det["onset"])[v]
        rows[:, 2] = np.asarray(det["n_stations"])[v]
        rows[:, 3] = np.asarray(det["score"])[v]
        rows[:, 5:7] = locate_mod.LOC_NONE
        rows[:, 7] = locate_mod.MAG_NONE
        onset_mat = (np.asarray(det["station_onset"])[v]
                     if self.locating else None)
        score_mat = (np.asarray(det["station_score"])[v]
                     if self.locating else None)
        if self._emitted.shape[0] and rows.shape[0]:
            near = ((np.abs(rows[:, 0, None] - self._emitted[None, :, 0])
                     <= acfg.dt_tol)
                    & (np.abs(rows[:, 1, None] - self._emitted[None, :, 1])
                       <= acfg.onset_tol))
            matched = near.any(axis=1)
            # best multiplicity this key has alerted at; a matched group
            # that now exceeds it re-emits as an upgrade
            best = np.where(matched,
                            (near * self._emitted[None, :, 2]).max(axis=1),
                            0)
            upgrade = matched & (rows[:, 2] > best)
            for r in np.nonzero(upgrade)[0]:
                js = np.nonzero(near[r])[0]
                self._emitted[js, 2] = np.maximum(self._emitted[js, 2],
                                                  rows[r, 2])
            rows[:, 4] = upgrade.astype(np.int64)
            keep = ~matched | upgrade
            rows = rows[keep]
            if self.locating:
                onset_mat, score_mat = onset_mat[keep], score_mat[keep]
        fresh = rows[rows[:, 4] == 0]
        if fresh.shape[0]:
            self._emitted = np.concatenate([self._emitted, fresh[:, :3]])
        if self.locating and rows.shape[0]:
            rows, _ = self._locate_rows(rows, onset_mat, score_mat)
        # onsets below every station's closed frontier minus the sliding
        # window can gain no further members — stop rescanning them, and
        # archive rows + dedup keys + amplitude bins the floor has passed
        # so the per-push scan stays O(active window) instead of O(stream)
        frontier = min(st.filter.w_start for st in self.stations)
        self._assoc_lo = max(self._assoc_lo, frontier
                             - self.scfg.window_fingerprints
                             - 2 * acfg.onset_tol)
        for st in self.stations:
            st.filter.retire_below(self._assoc_lo)
        if self._emitted.shape[0]:
            live = self._emitted[:, 1] >= self._assoc_lo - acfg.onset_tol
            self._emitted = self._emitted[live]
        amp_floor = self._assoc_lo - acfg.onset_tol
        if amp_floor > 0:
            for d in self._amp:
                for b in [b for b in d if b < amp_floor]:
                    del d[b]
        return rows

    def finalize(self) -> tuple[dict | None, list[Events], dict]:
        if self.pooled:
            self._pool_flush()
        station_events, stats = [], {}
        for i, st in enumerate(self.stations):
            events, _, fstats = st.finalize()
            station_events.append(events)
            for k, v in fstats.items():
                stats[f"station{i}_{k}"] = v
        detections = None
        if len(self.stations) >= 2:
            detections = align_mod.associate_network(
                station_events, self.cfg.align, len(self.stations),
                with_onsets=self.locating)
            if self.locating:
                t0 = time.perf_counter()
                fcfg = self.cfg.fingerprint
                was = int(np.asarray(detections["valid"]).sum())
                detections = locate_mod.attach_location(
                    detections, self.station_xy, self._station_weights(),
                    fcfg.lag_samples / fcfg.fs, self.cfg.locate,
                    self._amp_fn, stats)
                self.telemetry.record_locate(
                    groups=was,
                    located=int(np.asarray(detections["valid"]).sum()),
                    rejected=stats.get("moveout_rejected", 0),
                    wall=time.perf_counter() - t0)
            stats["detections"] = int(np.asarray(
                detections["valid"]).sum())
        if self.rolling:
            stats["alerts"] = int(sum(a.shape[0] for a in self.alerts))
        stats["ingest"] = [st.stats.summary() for st in self.stations]
        stats["quality"] = self.quality_summary()
        return detections, station_events, stats

    def quality_summary(self) -> dict:
        """Network-wide data-quality counters — the per-station summaries
        folded through the one shared aggregation path (same keys as
        ``StationStream.quality_summary``)."""
        return merge_counts(st.quality_summary() for st in self.stations)

    def metrics_snapshot(self) -> dict:
        """The single structured telemetry view of this detector (schema
        ``stream-metrics/v1``): aggregate + per-station throughput, the
        in-dispatch drop breakdown and rates, quality counters, wall-time
        histograms, span totals, and watchdog state. Consumed by
        ``serve_detect``, ``bench_stream``/``bench_e2e``, the examples,
        and the tier-1 schema test."""
        return tele_mod.metrics_snapshot(self)

    # -- snapshot / restore -------------------------------------------------

    def snapshot(self, ckpt_dir: str, step: int | None = None, *,
                 background: bool = False, keep: int = 3):
        """Checkpoint the whole detector through ``train/checkpoint.py``.

        One ``step_<N>`` directory holds every station's index pytree, ring
        buffer, MAD reservoir, pending blocks, and (bounded mode) rolling
        filter state, plus the detector's alert dedup keys — everything
        needed for ``restore`` to continue the stream bit-exactly. Pooled
        detectors snapshot per-station slices, so the on-disk layout is
        identical either way.
        """
        arrays: dict[str, np.ndarray] = {}
        st_extra = []
        for i, st in enumerate(self.stations):
            a, e = st.snapshot_state()
            arrays.update({f"s{i}/{k}": v for k, v in a.items()})
            st_extra.append(e)
        arrays["detector/emitted"] = self._emitted
        arrays["detector/alerts"] = (
            np.concatenate(self.alerts, axis=0).astype(np.int64)
            if self.alerts else np.zeros((0, ALERT_COLS), np.int64))
        for i, d in enumerate(self._amp):
            arrays[f"detector/amp{i}"] = (
                np.array([[b, a] for b, a in sorted(d.items())], np.float64)
                if d else np.zeros((0, 2), np.float64))
        extra = {"n_stations": len(self.stations), "stations": st_extra,
                 "assoc_lo": self._assoc_lo,
                 "telemetry": self.telemetry.snapshot(),
                 "scfg": {
                     "block_fingerprints": self.scfg.block_fingerprints,
                     "window_fingerprints": self.scfg.window_fingerprints,
                     "filter_window_fingerprints":
                         self.scfg.filter_window_fingerprints,
                     "reorder_horizon_samples":
                         self.scfg.reorder_horizon_samples,
                     "saturation_limit": self.scfg.saturation_limit,
                     "dup_window_fingerprints":
                         self.scfg.dup_window_fingerprints,
                     "dup_sig_tables": self.scfg.dup_sig_tables,
                     "occ_limit": self.scfg.occ_limit,
                     "max_pairs_per_block": self.scfg.max_pairs_per_block,
                     "verify_jaccard": int(self.scfg.verify_jaccard),
                 }}
        if step is None:
            step = self.stations[0].stats.chunks
        return ckpt_mod.save_checkpoint(ckpt_dir, step, arrays, extra=extra,
                                        background=background, keep=keep)

    @classmethod
    def restore(cls, ckpt_dir: str, cfg: DetectConfig,
                scfg: StreamConfig | None = None, *,
                step: int | None = None,
                station_xy: np.ndarray | None = None,
                ) -> tuple["StreamingDetector", int]:
        """Rebuild a detector from its latest (or given) snapshot.

        The snapshot records the streaming mode it was taken under; a
        ``scfg`` whose block size or window lengths differ is rejected up
        front (the station state layouts are not interchangeable).
        ``station_xy`` is not snapshotted (it is deployment geometry, not
        stream state) — pass it again to keep the locate tier running
        across the restart.
        """
        arrays, extra, step = ckpt_mod.restore_flat(ckpt_dir, step=step)
        det = cls(cfg, scfg, n_stations=int(extra["n_stations"]),
                  station_xy=station_xy)
        saved = extra.get("scfg", {})
        for key, have in (
                ("block_fingerprints", det.scfg.block_fingerprints),
                ("window_fingerprints", det.scfg.window_fingerprints),
                ("filter_window_fingerprints",
                 det.scfg.filter_window_fingerprints),
                ("reorder_horizon_samples",
                 det.scfg.reorder_horizon_samples),
                ("saturation_limit", det.scfg.saturation_limit),
                ("dup_window_fingerprints",
                 det.scfg.dup_window_fingerprints),
                ("dup_sig_tables", det.scfg.dup_sig_tables),
                ("occ_limit", det.scfg.occ_limit),
                # verify toggles the packed-fingerprint ring, which is
                # part of the station state layout (max_pairs is not —
                # it only shapes the per-step output, so it may differ)
                ("verify_jaccard", det.scfg.verify_jaccard)):
            if key in saved and int(saved[key]) != int(have):
                raise ValueError(
                    f"snapshot was taken with {key}={saved[key]} but the "
                    f"restoring StreamConfig has {have}; pass a matching "
                    f"config (e.g. the same --window-fp/--filter-window-fp "
                    f"flags the snapshotting service ran with)")
        for i, st in enumerate(det.stations):
            prefix = f"s{i}/"
            sub = {k[len(prefix):]: v for k, v in arrays.items()
                   if k.startswith(prefix)}
            st.restore_state(sub, extra["stations"][i])
        if det.pooled and all(st.stats_frozen for st in det.stations):
            det._build_pool()
        emitted = np.asarray(arrays["detector/emitted"], np.int64)
        if emitted.ndim == 2 and emitted.shape[1] == 2:
            # pre-ISSUE-9 snapshot: (k, 2) keys without a best-
            # multiplicity column — seed it at the floor, so any growth
            # past min_stations re-emits as an upgrade
            emitted = np.concatenate(
                [emitted, np.full((emitted.shape[0], 1),
                                  cfg.align.min_stations, np.int64)],
                axis=1)
        det._emitted = emitted.reshape(-1, 3)
        alerts = np.asarray(arrays["detector/alerts"], np.int64)
        if alerts.ndim == 2 and alerts.shape[1] == 4:
            # pre-ISSUE-9 snapshot: (k, 4) rows — pad the upgrade /
            # location / magnitude columns with their sentinels
            pad = np.zeros((alerts.shape[0], ALERT_COLS - 4), np.int64)
            pad[:, 1:3] = locate_mod.LOC_NONE
            pad[:, 3] = locate_mod.MAG_NONE
            alerts = np.concatenate([alerts, pad], axis=1)
        alerts = alerts.reshape(-1, ALERT_COLS)
        det.alerts = [alerts] if alerts.shape[0] else []
        for i in range(len(det.stations)):
            amp = arrays.get(f"detector/amp{i}")
            if amp is not None and amp.size:
                det._amp[i] = {int(b): float(a)
                               for b, a in np.asarray(amp).reshape(-1, 2)}
        det._assoc_lo = int(extra["assoc_lo"])
        if "telemetry" in extra:    # pre-ISSUE-6 snapshots: fresh registry
            det.telemetry.restore(extra["telemetry"])
        if det.rolling:
            det._polled_windows = sum(st.filter.windows_closed
                                      for st in det.stations)
        return det, step


def ingest_chunks(det: StreamingDetector, waveforms: np.ndarray,
                  n_chunks: int = 16, *, skip: int = 0,
                  warmup_chunks: int = 0, snapshot_every: int = 0,
                  snapshot_dir: str | None = None,
                  metrics_every: int = 0,
                  metrics_file: str | None = None,
                  heartbeat=print, on_chunk=None) -> dict:
    """Push a trace through a detector in equal chunks — the one shared
    ingest loop behind serving, benchmarks, and examples.

    ``waveforms``: (T,) or (n_stations, T). ``skip`` resumes mid-stream
    (samples already ingested before a snapshot restore are not re-pushed;
    a partially-covered chunk is trimmed). ``warmup_chunks`` excludes the
    first chunks (trace compilation + stats freeze) from the timed span.
    ``metrics_every`` > 0 turns on the live health surface: every N
    pushed chunks a heartbeat line (real-time factor, throughput, drop
    rates, quality counters) goes to ``heartbeat``, the span tracer's
    buffered JSONL records are flushed, and, when
    ``metrics_file`` is set, the Prometheus text exposition is rewritten
    atomically at the same cadence (a scrape never sees a torn file).
    ``on_chunk(ci)`` runs after each pushed chunk — the interleave hook
    the serving tier uses to admit arrivals, refresh its pool snapshot,
    and pump query ticks between ingest chunks (``ServeSession``).
    Returns {"chunks", "timed_chunks", "wall_s", "warmup_wall_s",
    "samples"}.
    """
    waveforms = np.atleast_2d(np.asarray(waveforms, np.float32))
    chunks = np.array_split(waveforms, n_chunks, axis=1)
    seen = 0
    pushed = timed = 0
    samples = 0
    t_start = time.perf_counter()
    t_timed = None
    for ci, chunk in enumerate(chunks):
        seen += chunk.shape[1]
        if seen <= skip:
            continue
        if seen - chunk.shape[1] < skip:
            chunk = chunk[:, chunk.shape[1] - (seen - skip):]
        if pushed == warmup_chunks and t_timed is None:
            t_timed = time.perf_counter()
        det.push(chunk)
        pushed += 1
        if pushed > warmup_chunks:
            timed += 1
            samples += int(chunk.size)
        if snapshot_every and (ci + 1) % snapshot_every == 0:
            det.snapshot(snapshot_dir, step=ci + 1)
        if metrics_every and pushed % metrics_every == 0:
            heartbeat(det.telemetry.heartbeat_line(det))
            # the buffered span records reach the log at each heartbeat
            det.telemetry.tracer.flush()
            if metrics_file:
                det.telemetry.write_prometheus(metrics_file, det)
        if on_chunk is not None:
            on_chunk(ci)
    t_end = time.perf_counter()
    if t_timed is None:
        t_timed = t_end
    return {"chunks": pushed, "timed_chunks": timed,
            "wall_s": t_end - t_timed,
            "warmup_wall_s": t_timed - t_start, "samples": samples}
