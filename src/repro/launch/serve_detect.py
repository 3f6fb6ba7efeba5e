"""Concurrent, backpressured query serving over a streaming LSH index pool.

The detection-side sibling of ``launch/serve.py``, grown into a service
tier (ISSUE 7): requests are *query windows* of raw waveform ("when did
something like this happen?") answered against the per-station
``StreamingIndex`` pool built by continuous ingestion. The tier has three
layers:

* **admission queue** (``ServeDetectEngine.submit``): a bounded FIFO in
  front of the slots. Depth past ``max_queue`` load-sheds the request —
  it completes immediately with ``outcome="rejected"`` instead of growing
  host state without bound under overload. Every request carries
  arrival-time accounting: queue wait (submit → slot admission) and
  service time (admission → completion) are split so the latency
  histograms say *where* time went.
* **batched ticks** (``ServeDetectEngine.tick``): each tick admits queued
  requests into free slots and runs **one** jitted ``_serve_step``
  dispatch that fingerprints every active slot once and queries it
  against *every* station's index (read-only — serving never mutates the
  pool). Concurrent requests share device dispatches exactly like decode
  slots share a decode step; S stations cost one vmapped dispatch, not S.
  Idle ticks (no active slots) return without assembling a batch or
  dispatching at all.
* **interleaved ingestion** (``ServeSession``): the cooperative
  single-process service loop — ingest chunks keep growing the corpus
  while query ticks run between them, against a read-only
  ``pool_serving_state()`` snapshot refreshed at a configurable cadence
  (``refresh_every_chunks``; version-gated, so an unchanged detector
  costs nothing). The shape is qseek's asyncio search loop without the
  event loop: two duties, one thread, explicit yield points.

Telemetry publishes through the PR-6 substrate, never ad-hoc counters:
``serve_requests_total{outcome=accepted|shed|served}``, per-tick
``serve_queue_depth``/``serve_active_slots`` gauges,
``serve_{latency,queue_wait,service}_seconds`` histograms and
``serve_state_refreshes_total`` all land in the detector's
``repro.obsv`` registry, so the heartbeat, the Prometheus exposition and
``metrics_snapshot()["serve"]`` carry the serving tier for free.

Restartable service flags:

  ``--stations N``        stations ingested and served (the pool's S
                          axis). With ``--restore`` it must match the
                          snapshot's pool width — a mismatched width is
                          rejected up front instead of silently serving
                          the wrong pool.
  ``--snapshot-every N``  checkpoint the ingesting detector every N
                          chunks via ``train/checkpoint.py`` into
                          ``--snapshot-dir``.
  ``--restore``           resume ingestion from the latest snapshot in
                          ``--snapshot-dir`` (only post-snapshot samples
                          re-ingest).
  ``--window-fp N``       sliding detection window (index expiry).
  ``--filter-window-fp N``  rolling occurrence-filter window.
  ``--occ-limit N``       in-dispatch §6.5 partner-collision cap.

Service-tier flags (ISSUE 7):

  ``--slots N``           concurrent request slots per batched dispatch.
  ``--max-queue N``       admission-queue bound; requests beyond it shed
                          with ``outcome="rejected"``.
  ``--interleave``        serve queries *while* ingesting (requests
                          arrive spread over the stream) instead of the
                          two-phase ingest-then-serve default.
  ``--refresh-every N``   chunks between serving-state refreshes in
                          interleaved mode.

Live health surface (ISSUE 6):

  ``--metrics-every N``   every N ingested chunks, print a ``HEARTBEAT``
                          JSON line built from ``StreamTelemetry``.
  ``--metrics-file P``    atomically rewrite ``P`` with the Prometheus
                          text exposition — at the heartbeat cadence when
                          ``--metrics-every`` is set, and always once
                          after ingest (a bare ``--metrics-file`` does a
                          final write instead of silently nothing).
  ``--trace-jsonl P``     append the span tree of every push (ingest,
                          dedup, the step's put/dispatch/wait/pull, host
                          tail, detections; ids, parents and realtime
                          ns, see ``repro.obsv.spans``) as JSONL to
                          ``P``, buffered in memory and flushed at every
                          ``--metrics-every`` heartbeat and at exit.
  ``--dirty``             ingest the fault-injected scenario stream
                          through the quality-hardened config.
  ``--locate``            located alert rows (ISSUE 9): the synthetic
                          network gets physical station geometry, the
                          ingesting detector runs the location /
                          magnitude tier, and every live alert prints as
                          an ``ALERT`` JSON line carrying origin (km),
                          relative magnitude and the upgrade flag, with
                          an aggregate ``located`` block in the RESULT.

Usage:
  PYTHONPATH=src python -m repro.launch.serve_detect --requests 12
  PYTHONPATH=src python -m repro.launch.serve_detect \
      --interleave --requests 16 --max-queue 8      # backpressured live
  PYTHONPATH=src python -m repro.launch.serve_detect \
      --snapshot-every 4 --snapshot-dir /tmp/fast_snap     # then kill …
  PYTHONPATH=src python -m repro.launch.serve_detect \
      --restore --snapshot-dir /tmp/fast_snap              # … and resume
"""
from __future__ import annotations

import argparse
import collections
import functools
import json
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.fast_seismic import smoke_config, stream_smoke_config
from repro.core import fingerprint as fp_mod
from repro.core import lsh as lsh_mod
from repro.core.detect import DetectConfig
from repro.core.fingerprint import FingerprintConfig
from repro.core.lsh import INVALID, LSHConfig
from repro.core.synth import SynthConfig, make_dataset
from repro.stream import index as index_mod
from repro.stream.engine import StreamingDetector, ingest_chunks
from repro.stream.index import IndexState
from repro.stream.ingest import StreamConfig
from repro.stream.telemetry import StreamTelemetry

# completed-request latency samples retained for exact percentiles; the
# registry histograms keep the full-lifetime (bucketed) view, so the
# engine's own memory stays O(1) on an unbounded request stream
LATENCY_WINDOW = 65536


@dataclass
class ServeConfig:
    """Serving-tier knobs (see ``configs.fast_seismic.serve_smoke_config``
    / ``serve_config`` for the smoke and paper-scale instantiations)."""
    n_slots: int = 4            # concurrent slots per batched dispatch
    max_queue: int = 64         # admission bound; beyond it requests shed
    top_k: int = 32             # matches returned per (station, block)
    refresh_every_chunks: int = 4   # interleaved serving-state cadence


@dataclass
class QueryRequest:
    rid: int
    window: np.ndarray            # raw waveform samples
    matches: list = field(default_factory=list)  # (station, fp_id, sim)
    ticks: int = 0
    done: bool = False
    outcome: str = "pending"      # pending | active | served | rejected
    t_submit: float = 0.0
    t_admit: float = 0.0          # dequeued into a slot
    t_done: float = 0.0

    @property
    def queue_wait_s(self) -> float:
        """Submit → slot admission (0.0 while still queued or shed)."""
        if self.t_admit <= 0.0:
            return 0.0
        return self.t_admit - self.t_submit

    @property
    def service_s(self) -> float:
        """Slot admission → completion (0.0 while in flight)."""
        if self.t_done <= 0.0 or self.t_admit <= 0.0:
            return 0.0
        return self.t_done - self.t_admit

    @property
    def latency_s(self) -> float:
        """Submit → completion; 0.0 for unfinished requests (an unset
        ``t_done`` used to yield a negative wall-clock delta)."""
        if self.t_done <= 0.0:
            return 0.0
        return self.t_done - self.t_submit


@functools.partial(jax.jit,
                   static_argnames=("fcfg", "lcfg", "top_k", "max_pairs"))
def _serve_step(state: IndexState, blocks: jax.Array, med: jax.Array,
                mad: jax.Array, mappings: jax.Array, slot_valid: jax.Array,
                fcfg: FingerprintConfig, lcfg: LSHConfig, top_k: int = 32,
                max_pairs: int = 0):
    """(n_slots, block_samples) slot blocks × (S,)-pooled index state →
    per-(station, slot) (ids, sims) match tables, each (S, n_slots, top_k).

    The raw-coefficient half of the fingerprint chain runs once per slot
    and is shared across stations; only binarization (per-station §5.2
    statistics), signatures, and the index gather run under the station
    vmap. Query fingerprints get ids above any corpus id, so the index's
    id-ordered emission returns every stored partner; invalid slots get
    filler signatures and match nothing.

    ``max_pairs`` > 0 (ISSUE 8) compacts each slot's emission in-dispatch
    before ranking: the ``top_k`` reduction then runs over ``max_pairs``
    candidate rows instead of the dense t * N * C slot tensor. Sized
    comfortably above the expected per-query match count (config default:
    several × top_k × n_tables) the match tables are identical — overflow
    past the bound drops lexicographically-largest candidates first.
    """
    coeffs = jax.vmap(lambda b: fp_mod.coeffs_from_waveform(b, fcfg))(blocks)

    def per_station(st_state, st_med, st_mad):
        def one_slot(c, valid):
            bits, _ = fp_mod.binarize_coeffs(c, fcfg, (st_med, st_mad))
            n = bits.shape[0]
            sigs = lsh_mod.signatures(bits, mappings, lcfg, valid=valid)
            # distinct ids above every corpus id → each window fingerprint
            # pairs with all of its stored partners
            qids = jnp.int32(INVALID - 1 - n) + jnp.arange(n, dtype=jnp.int32)
            pairs = index_mod.query(st_state, sigs, qids, lcfg,
                                    max_pairs=max_pairs)
            sims = jnp.where(pairs.valid, pairs.sim, 0)
            top = jax.lax.top_k(sims, k=min(top_k, sims.shape[0]))[1]
            return pairs.idx1[top], sims[top]

        return jax.vmap(one_slot)(coeffs, slot_valid)

    return jax.vmap(per_station)(state, med, mad)


class ServeDetectEngine:
    """Admission queue + static slots + one batched dispatch per tick.

    ``state``/``med``/``mad`` carry a leading station axis
    (``StreamingDetector.pool_serving_state``). The state may start
    ``None`` (interleaved serving before the detector's statistics
    freeze): requests queue, and ticks are idle until the first
    ``refresh``/``refresh_from`` installs a pool.
    """

    def __init__(self, cfg: DetectConfig, scfg: StreamConfig,
                 state: IndexState | None = None, med_mad=None,
                 n_slots: int = 4, top_k: int = 32, max_queue: int = 64,
                 telemetry: StreamTelemetry | None = None,
                 clock=time.perf_counter):
        self.cfg = cfg
        self.scfg = scfg
        self.telemetry = telemetry or StreamTelemetry(0)
        self.clock = clock
        self.state: IndexState | None = None
        self.med = self.mad = None
        self.n_stations = 0
        self.serving_version = -1   # detector version the pool mirrors
        self.mappings = lsh_mod.hash_mappings(cfg.fingerprint.fp_dim,
                                              cfg.lsh)
        self.n_slots = n_slots
        self.top_k = top_k
        # compacted slot queries (0 = dense): never below top_k, or the
        # (S, slots, top_k) match-table shape itself would shrink
        self.max_pairs = (0 if scfg.max_pairs_per_block == 0
                          else max(scfg.max_pairs_per_block, top_k))
        self.max_queue = max_queue
        self.block_samples = cfg.fingerprint.block_samples(
            scfg.block_fingerprints)
        # cached filler rows: idle slots never allocate per tick
        self._zero_block = np.zeros(self.block_samples, np.float32)
        self._zero_mask = np.zeros(scfg.block_fingerprints, bool)
        self.slot_req: list[QueryRequest | None] = [None] * n_slots
        self.slot_blocks: list[list] = [[] for _ in range(n_slots)]
        self.queue: collections.deque[QueryRequest] = collections.deque()
        self.ticks = 0
        self.dispatches = 0
        self.slot_ticks = 0         # Σ active slots over dispatches
        self.submitted = self.served = self.shed = 0
        self.lat = {k: collections.deque(maxlen=LATENCY_WINDOW)
                    for k in ("queue_wait_s", "service_s", "latency_s")}
        if state is not None:
            self._install(state, med_mad)

    @classmethod
    def from_detector(cls, det: StreamingDetector, **kw
                      ) -> "ServeDetectEngine":
        """Engine over a detector's current pool, sharing its telemetry
        registry (one health surface for ingest + serving)."""
        eng = cls(det.cfg, det.scfg, telemetry=det.telemetry, **kw)
        eng.refresh_from(det)
        return eng

    # -- serving state -------------------------------------------------------

    def _install(self, state: IndexState, med_mad) -> None:
        med = jnp.asarray(med_mad[0])
        assert med.ndim == 2 and state.sig.ndim == 4, \
            "serving state must be pooled (leading station axis)"
        if self.n_stations and med.shape[0] != self.n_stations:
            raise ValueError(
                f"refresh changed the pool width: serving {self.n_stations}"
                f" stations, refresh has {med.shape[0]}")
        self.state = state
        self.med = med
        self.mad = jnp.asarray(med_mad[1])
        self.n_stations = med.shape[0]

    def refresh(self, state: IndexState, med_mad, version: int = -1) -> None:
        """Install a new read-only pool snapshot (queries from the next
        tick on see the grown corpus)."""
        self._install(state, med_mad)
        self.serving_version = version
        self.telemetry.record_serve_refresh()

    def refresh_from(self, det: StreamingDetector) -> bool:
        """Version-gated refresh from an ingesting detector: a no-op
        until its statistics freeze, and when no chunk arrived since the
        pool snapshot this engine already serves. A detector whose
        stations record several components is refused: the engine's
        answers are per pool member, which would be components, not
        stations."""
        if det.channels > 1:
            raise ValueError(
                f"serving answers per station, and this detector's "
                f"stations record {det.channels} components each; serve "
                f"a one-component detector (channels=1)")
        if not all(st.stats_frozen for st in det.stations):
            return False
        if det.serving_version == self.serving_version:
            return False
        state, med, mad = det.pool_serving_state()
        self.refresh(state, (med, mad), version=det.serving_version)
        return True

    # -- admission -----------------------------------------------------------

    def submit(self, req: QueryRequest) -> bool:
        """Admission control: enqueue, or load-shed past ``max_queue``.

        A shed request completes immediately with ``outcome="rejected"``
        — bounded queue depth is the overload contract (the service
        answers *something* fast rather than queueing without bound).
        """
        now = self.clock()
        req.t_submit = now
        self.submitted += 1
        if len(self.queue) >= self.max_queue:
            req.done = True
            req.outcome = "rejected"
            req.t_done = now
            self.shed += 1
            self.telemetry.record_serve_admission(False)
            return False
        self.queue.append(req)
        self.telemetry.record_serve_admission(True)
        return True

    def active(self) -> bool:
        return any(r is not None for r in self.slot_req)

    def pending(self) -> int:
        """Requests not yet completed (queued + in slots)."""
        return len(self.queue) + sum(r is not None for r in self.slot_req)

    def _admit(self) -> None:
        for slot in range(self.n_slots):
            if self.slot_req[slot] is None and self.queue:
                req = self.queue.popleft()
                req.t_admit = self.clock()
                req.outcome = "active"
                self.slot_req[slot] = req
                self.slot_blocks[slot] = self._split_blocks(req.window)

    def _split_blocks(self, window: np.ndarray
                      ) -> list[tuple[np.ndarray, np.ndarray]]:
        """Fixed-size (block, fp_valid_mask) covering the window.

        Tails are zero-padded; the mask marks fingerprints whose analysis
        window lies fully inside real samples, so padding never queries.
        """
        fcfg = self.cfg.fingerprint
        n_fp = self.scfg.block_fingerprints
        bs, adv = self.block_samples, n_fp * fcfg.lag_samples
        blocks, start = [], 0
        while start == 0 or start + fcfg.window_samples <= window.size:
            blk = np.zeros(bs, np.float32)
            seg = window[start: start + bs]
            blk[: seg.size] = seg
            avail = window.size - start
            n_valid = max(0, min(
                n_fp, (avail - fcfg.window_samples) // fcfg.lag_samples + 1))
            blocks.append((blk, np.arange(n_fp) < n_valid))
            start += adv
        return blocks

    # -- the batched tick ----------------------------------------------------

    def tick(self) -> int:
        """One service tick: admit queued requests into free slots, run at
        most ONE batched ``_serve_step`` dispatch over every active slot,
        and complete requests whose last block was answered. Returns the
        number of slots served; an idle tick (nothing active) returns 0
        without assembling a batch or dispatching.
        """
        if self.state is not None:
            self._admit()
        active = [s for s in range(self.n_slots)
                  if self.slot_req[s] is not None]
        self.ticks += 1
        self.telemetry.record_serve_tick(len(active), len(self.queue))
        if not active:
            return 0
        batch = np.stack([
            self.slot_blocks[s][0][0] if self.slot_req[s] is not None
            else self._zero_block for s in range(self.n_slots)])
        slot_valid = jnp.asarray(np.stack([
            self.slot_blocks[s][0][1] if self.slot_req[s] is not None
            else self._zero_mask for s in range(self.n_slots)]))
        ids, sims = _serve_step(
            self.state, jnp.asarray(batch), self.med, self.mad,
            self.mappings, slot_valid, self.cfg.fingerprint,
            self.cfg.lsh, self.top_k, self.max_pairs)
        self.dispatches += 1
        self.slot_ticks += len(active)
        ids_h, sims_h = np.asarray(ids), np.asarray(sims)  # (S, slots, k)
        for slot in active:
            req = self.slot_req[slot]
            for station in range(self.n_stations):
                keep = sims_h[station, slot] > 0
                req.matches.extend(
                    (station, int(i), int(s))
                    for i, s in zip(ids_h[station, slot][keep],
                                    sims_h[station, slot][keep]))
            req.ticks += 1
            self.slot_blocks[slot].pop(0)
            if not self.slot_blocks[slot]:
                self._complete(slot)
        return len(active)

    def _complete(self, slot: int) -> None:
        req = self.slot_req[slot]
        req.done = True
        req.outcome = "served"
        req.t_done = self.clock()
        self.slot_req[slot] = None
        self.served += 1
        self.lat["queue_wait_s"].append(req.queue_wait_s)
        self.lat["service_s"].append(req.service_s)
        self.lat["latency_s"].append(req.latency_s)
        self.telemetry.record_serve_done(req.queue_wait_s, req.service_s,
                                         req.latency_s)

    def drain(self) -> None:
        """Tick until every admitted request completes."""
        assert self.state is not None or not self.pending(), \
            "cannot drain before a serving state is installed"
        while self.pending():
            self.tick()

    # -- summaries -----------------------------------------------------------

    def run(self, requests: list[QueryRequest]) -> dict:
        """Two-phase convenience path: submit everything at once (the
        all-requests-arrive-together burst), drain, summarize."""
        t0 = self.clock()
        for r in requests:
            self.submit(r)
        self.drain()
        return self.summary(requests, self.clock() - t0)

    def summary(self, requests: list[QueryRequest], wall_s: float) -> dict:
        served = [r for r in requests if r.outcome == "served"]

        def pct(vals, q):
            if not vals:        # empty request list / everything shed
                return 0.0
            return round(float(np.percentile(vals, q)) * 1e3, 2)

        lats = [r.latency_s for r in served]
        waits = [r.queue_wait_s for r in served]
        svc = [r.service_s for r in served]
        return {
            "requests": len(requests),
            "served": len(served),
            "shed": sum(1 for r in requests if r.outcome == "rejected"),
            "stations": self.n_stations,
            "ticks": self.ticks,
            "dispatches": self.dispatches,
            "wall_s": round(wall_s, 3),
            "requests_per_s": round(len(served) / max(wall_s, 1e-9), 1),
            "latency_ms_p50": pct(lats, 50),
            "latency_ms_p95": pct(lats, 95),
            "latency_ms_p99": pct(lats, 99),
            "queue_wait_ms_p50": pct(waits, 50),
            "queue_wait_ms_p99": pct(waits, 99),
            "service_ms_p50": pct(svc, 50),
            "service_ms_p99": pct(svc, 99),
            "hit_requests": sum(1 for r in served if r.matches),
        }


class ServeSession:
    """Cooperative ingest + serve loop (qseek's asyncio search-loop shape
    on one thread): chunks keep growing the corpus while query ticks run
    between them against a refreshed read-only pool snapshot.

    ``after_push()`` is the per-chunk duty cycle — refresh the engine's
    serving state at the configured cadence (version-gated; a no-op until
    the detector's statistics freeze) and pump up to ``ticks_per_chunk``
    query ticks. ``finish()`` flushes the detector, takes the final
    refresh, and drains the queue.
    """

    def __init__(self, det: StreamingDetector, engine: ServeDetectEngine,
                 refresh_every_chunks: int = 4, ticks_per_chunk: int = 2):
        self.det = det
        self.engine = engine
        self.refresh_every_chunks = max(1, refresh_every_chunks)
        self.ticks_per_chunk = ticks_per_chunk
        self.chunks = 0
        self.refreshes = 0

    def submit(self, req: QueryRequest) -> bool:
        return self.engine.submit(req)

    def ingest(self, chunk: np.ndarray, offset: int | None = None) -> None:
        self.det.push(chunk, offset)
        self.after_push()

    def after_push(self) -> None:
        self.chunks += 1
        if self.chunks % self.refresh_every_chunks == 0:
            self.refreshes += int(self.engine.refresh_from(self.det))
        self.pump(self.ticks_per_chunk)

    def pump(self, max_ticks: int) -> int:
        """Run up to ``max_ticks`` query ticks; stops early when nothing
        is pending or no serving state exists yet."""
        n = 0
        while (n < max_ticks and self.engine.state is not None
               and self.engine.pending()):
            self.engine.tick()
            n += 1
        return n

    def finish(self) -> None:
        self.det.flush()
        self.refreshes += int(self.engine.refresh_from(self.det))
        self.engine.drain()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-queue", type=int, default=64,
                    help="admission-queue bound (beyond it requests shed)")
    ap.add_argument("--interleave", action="store_true",
                    help="serve queries while ingesting (requests arrive "
                         "spread over the stream) instead of after it")
    ap.add_argument("--refresh-every", type=int, default=4,
                    help="chunks between serving-state refreshes "
                         "(interleaved mode)")
    ap.add_argument("--stations", type=int, default=2,
                    help="stations ingested + served (index pool S axis)")
    ap.add_argument("--duration-s", type=float, default=600.0)
    ap.add_argument("--window-s", type=float, default=20.0)
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="checkpoint the ingesting detector every N chunks")
    ap.add_argument("--snapshot-dir", default="/tmp/fast_serve_snapshots")
    ap.add_argument("--restore", action="store_true",
                    help="resume ingestion from the latest snapshot")
    ap.add_argument("--window-fp", type=int, default=0,
                    help="sliding detection window (fingerprints; 0 = off)")
    ap.add_argument("--filter-window-fp", type=int, default=0,
                    help="rolling occurrence-filter window (0 = finalize)")
    ap.add_argument("--occ-limit", type=int, default=0,
                    help="in-dispatch §6.5 partner-collision cap (0 = off)")
    ap.add_argument("--metrics-every", type=int, default=0,
                    help="heartbeat + exposition cadence in chunks (0=off)")
    ap.add_argument("--metrics-file", default=None,
                    help="Prometheus text exposition path (atomic rewrite)")
    ap.add_argument("--trace-jsonl", default=None,
                    help="append structured span records (JSONL) here")
    ap.add_argument("--dirty", action="store_true",
                    help="ingest the fault-injected scenario stream "
                         "through the quality-hardened config")
    ap.add_argument("--locate", action="store_true",
                    help="station geometry + location/magnitude tier: "
                         "alerts carry a migration-stacked origin and a "
                         "relative magnitude (defaults "
                         "--filter-window-fp 64 so alerts emit live)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.locate:
        from repro.configs.fast_seismic import located_smoke_config
        cfg = located_smoke_config()
        # live alerts need the bounded regime: a sliding index window
        # plus the rolling occurrence filter (same shape as
        # stream_bounded_smoke_config)
        if not args.window_fp:
            args.window_fp = 128
        if not args.filter_window_fp:
            args.filter_window_fp = 64
    else:
        cfg = smoke_config()
    if args.dirty:
        from repro.configs.fast_seismic import stream_dirty_smoke_config
        scfg = stream_dirty_smoke_config()
    else:
        scfg = stream_smoke_config()
    if args.window_fp or args.filter_window_fp or args.occ_limit:
        import dataclasses
        icfg = scfg.index
        if args.occ_limit:
            # ring spans everything a pair can reach back over: the
            # sliding window when set, else the whole ingested corpus
            n_fp = int(args.duration_s * cfg.fingerprint.fs
                       / cfg.fingerprint.lag_samples) + 1
            icfg = dataclasses.replace(
                icfg, occ_slots=args.window_fp or n_fp)
        scfg = dataclasses.replace(
            scfg, window_fingerprints=args.window_fp,
            filter_window_fingerprints=args.filter_window_fp,
            occ_limit=args.occ_limit, index=icfg)
    base = SynthConfig(duration_s=args.duration_s,
                       n_stations=args.stations,
                       n_sources=2, events_per_source=5,
                       event_snr=3.0, seed=3,
                       physical_geometry=args.locate)
    if args.dirty:
        # the pinned pathology mix of the scenario benchmark: telemetry
        # gaps, a duplicated block, one long repeating glitch train
        from repro.core.synth import ScenarioConfig, make_scenario_dataset
        scen = make_scenario_dataset(ScenarioConfig(
            base=base, n_gaps=2, gap_dur_s=(2.0, 5.0),
            n_dup_blocks=1, dup_block_dur_s=20.0, dup_spacing_s=60.0,
            glitch_stations=(0,), glitch_trains=1,
            glitch_train_dur_s=args.duration_s / 4.0, seed=1))
        ds, ingest_wf = scen.clean, scen.waveforms
    else:
        ds = make_dataset(base)
        ingest_wf = ds.waveforms

    # build the corpus index pool by streaming the stations in (resuming
    # from the latest snapshot when asked — only post-snapshot samples
    # re-ingest); the ingest loop is shared with the benchmarks
    station_xy = ds.station_xy if args.locate else None
    skip = 0
    if args.restore:
        det, step = StreamingDetector.restore(args.snapshot_dir, cfg, scfg,
                                              station_xy=station_xy)
        if args.stations > len(det.stations) and det.pooled \
                and all(st.stats_frozen for st in det.stations):
            # width growth is no longer a conflict: the pool is elastic
            # (ISSUE 10) — pad the restored snapshot with fresh stations
            # joining at the frontier, re-sharded over the current mesh
            grown = args.stations - len(det.stations)
            for _ in range(grown):
                det.add_station()
            print(f"# restored pool grown {len(det.stations) - grown}"
                  f" -> {len(det.stations)} stations (elastic re-shard)")
        elif len(det.stations) != args.stations:
            raise SystemExit(
                f"--restore: the snapshot holds a {len(det.stations)}-"
                f"station index pool but --stations {args.stations} was "
                f"requested; shrinking would discard station identities "
                f"irrecoverably — rerun with --stations "
                f"{len(det.stations)} (or take a fresh snapshot at the "
                f"new width)")
        skip = det.stations[0].ring.samples_in
        print(f"# restored step {step}: {skip} samples already ingested")
    else:
        det = StreamingDetector(cfg, scfg, n_stations=args.stations,
                                station_xy=station_xy)
    if args.trace_jsonl:
        from repro.obsv.spans import SpanTracer
        det.telemetry.tracer = SpanTracer(jsonl_path=args.trace_jsonl)

    # query windows centered on known event arrivals (+ random controls)
    wf = ds.waveforms[0]
    rng = np.random.default_rng(0)
    win = int(args.window_s * cfg.fingerprint.fs)
    reqs = []
    for i in range(args.requests):
        if i < len(ds.event_times):
            t0 = int(ds.arrival_time(i, 0) * cfg.fingerprint.fs)
        else:
            t0 = int(rng.integers(0, wf.size - win))
        lo = max(0, min(t0, wf.size - win))
        reqs.append(QueryRequest(rid=i, window=wf[lo: lo + win]))

    eng = ServeDetectEngine(cfg, scfg, n_slots=args.slots,
                            max_queue=args.max_queue,
                            telemetry=det.telemetry)
    n_chunks = 16
    t_serve = time.perf_counter()
    if args.interleave:
        # the service loop: requests arrive spread over ingestion and are
        # answered against the refreshed pool while the corpus grows
        session = ServeSession(det, eng,
                               refresh_every_chunks=args.refresh_every)
        arrival_chunk = [min(n_chunks - 1, i * n_chunks // max(
            len(reqs), 1)) for i in range(len(reqs))]
        next_req = [0]

        def on_chunk(ci: int) -> None:
            while (next_req[0] < len(reqs)
                   and arrival_chunk[next_req[0]] <= ci):
                session.submit(reqs[next_req[0]])
                next_req[0] += 1
            session.after_push()

        ingest_chunks(det, ingest_wf, n_chunks=n_chunks, skip=skip,
                      snapshot_every=args.snapshot_every,
                      snapshot_dir=args.snapshot_dir,
                      metrics_every=args.metrics_every,
                      metrics_file=args.metrics_file,
                      on_chunk=on_chunk)
        for r in reqs[next_req[0]:]:
            session.submit(r)
        session.finish()
    else:
        ingest_chunks(det, ingest_wf, n_chunks=n_chunks, skip=skip,
                      snapshot_every=args.snapshot_every,
                      snapshot_dir=args.snapshot_dir,
                      metrics_every=args.metrics_every,
                      metrics_file=args.metrics_file)
        det.flush()
    assert all(st.stats_frozen for st in det.stations), \
        "ingest too short to freeze MAD statistics"
    # data-quality reconciliation + guard counters (gaps spliced/dropped,
    # duplicates suppressed, saturated buckets hit) — the operational view
    # of how dirty the ingested telemetry was
    quality = det.quality_summary()
    print("# ingest quality " + json.dumps(quality))
    located_summary = None
    if args.locate:
        # the widened ISSUE-9 alert rows: location (milli-km sentinels
        # decoded to km), relative magnitude and the upgrade flag, one
        # JSON line per alert + an aggregate block in the RESULT stats
        from repro.core.locate import LOC_NONE, MAG_NONE
        lag_s = cfg.fingerprint.lag_samples / cfg.fingerprint.fs
        alert_rows = []
        for rows in det.alerts:
            for dt, onset, n_st, score, upg, x_mkm, y_mkm, mag_m in rows:
                alert_rows.append({
                    "t_s": round(float(onset) * lag_s, 1),
                    "dt_s": round(float(dt) * lag_s, 1),
                    "stations": int(n_st), "score": int(score),
                    "upgrade": bool(upg),
                    "x_km": None if x_mkm == LOC_NONE else x_mkm / 1e3,
                    "y_km": None if y_mkm == LOC_NONE else y_mkm / 1e3,
                    "dmag": None if mag_m == MAG_NONE else mag_m / 1e3,
                })
        for row in alert_rows:
            print("ALERT " + json.dumps(row))
        loc = [r for r in alert_rows if r["x_km"] is not None]
        errs = [float(np.min(np.linalg.norm(
                    ds.source_xy - np.array([r["x_km"], r["y_km"]]),
                    axis=1))) for r in loc]
        lv = det.telemetry.locate_view()
        located_summary = {
            "alerts": len(alert_rows),
            "located": len(loc),
            "upgrades": int(sum(r["upgrade"] for r in alert_rows)),
            "moveout_rejected": lv["moveout_rejected"],
            "locate_passes": lv["passes"],
            "median_origin_err_km": (round(float(np.median(errs)), 2)
                                     if errs else None),
        }
    if args.metrics_every:
        # final post-flush heartbeat so the log reflects the completed
        # ingest
        print(det.telemetry.heartbeat_line(det))
    if args.metrics_file:
        # the final exposition rewrite runs whenever a scrape file was
        # asked for — a bare --metrics-file used to write nothing
        det.telemetry.write_prometheus(args.metrics_file, det)
    det.telemetry.tracer.flush()

    if args.interleave:
        stats = eng.summary(reqs, time.perf_counter() - t_serve)
        stats["refreshes"] = int(eng.telemetry.registry.total(
            "serve_state_refreshes_total"))
    else:
        eng.refresh_from(det)
        stats = eng.run(reqs)
    assert all(r.done for r in reqs)
    stats["ingest_quality"] = quality
    if located_summary is not None:
        stats["located"] = located_summary
    if args.metrics_every:
        stats["metrics"] = det.metrics_snapshot()
    det.telemetry.tracer.close()
    print("RESULT " + json.dumps(stats))
    return stats


if __name__ == "__main__":
    main()
