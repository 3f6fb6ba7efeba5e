"""Streaming detection subsystem: FAST as a continuous service.

The paper's pipeline is strictly batch — fingerprint everything, sort
everything, then search — so a decade of history is re-sorted whenever one
new week of data arrives (§6.4 exists to make that giant sort fit in
memory). This package re-expresses detection as *query-against-index* over
an unbounded stream:

``ingest``   ``WaveformRing`` turns arbitrary-length chunks into fixed
             fingerprint blocks with the exact STFT halo across
             boundaries, and ``StreamingMAD`` keeps the §5.2 median/MAD
             statistics as a uniform reservoir (no second pass).

``index``    ``StreamingIndex``: the LSH hash tables materialized as
             fixed-capacity device-resident bucket arrays with jitted
             O(batch) ``insert``/``query`` (ring-buffer eviction caps
             mega-buckets structurally). Pair semantics — min_dt,
             m-of-t matches — are shared with the offline search via
             ``core.lsh.finalize_pairs``.

``engine``   ``StreamingDetector`` composes ring → fingerprints →
             signatures → insert+query → incremental pair accumulation →
             the offline alignment stack, per station, with per-chunk
             latency/throughput stats.

``fused``    the single-dispatch hot path (ISSUE 3): the whole per-block
             chain above as **one** jitted step over a donated
             ``FusedState`` pytree, plus the vmapped station pool.

Hot path anatomy — the one-dispatch invariant, one core two drivers
-------------------------------------------------------------------

Steady state (statistics frozen, no flush pending) must stay a *single*
device dispatch per block, per detector. The traced program is::

  step_advance(FusedState{index, halo, med, mad}, new_samples)
    wave   = concat(halo, new_samples)          # WaveformRing advance
    coeffs = haar2d(spectral_images(stft(wave)))  # fingerprint chain
    bits   = topk_binarize((coeffs - med) / mad)  # §5.2 binarization
    sig,bk = signatures_and_buckets(bits)       # Min-Max fold + addressing
    index  = insert(expire(index), sig, bk)     # sliding window + decay
    pairs  = query(index, sig, bk)              # id-ordered emission
    pairs  = occurrence_limit(index, pairs)     # in-dispatch §6.5 limiter
    pairs  = verify(compact(pairs))             # bounded emission + exact
    return FusedState{index', wave[-halo:], med, mad}, pairs, qc  # Jaccard

(the expire/guards/insert/query/limit tail is ``index.guarded_step``; the
duplicate probe and saturation quarantine run inside it, and with every
knob at 0 the whole tail compiles down to the unguarded program exactly).

Every ``FusedState`` leaf is **donated**: chunk N+1 overwrites chunk N's
buffers in place (zero steady-state HBM allocation), and the halo — the
STFT overlap between consecutive blocks — never leaves the device. Multi-
station detectors stack the state on a leading S axis and run the same
program under ``vmap`` (``pool_step_advance``): S stations, one dispatch.
Signature fold + bucket addressing are computed once and shared by insert
and query (and fuse into the Pallas Min-Max kernel epilogue on TPU).

**Batch = replay.** This is the repo's ONLY detection core (ISSUE 5):
the offline pipeline, ``core.detect.detect_events``, is a thin batch
driver that stacks an archive's stations and drives whole-trace blocks
through ``pool_step_block`` — the legacy host-orchestrated per-station
fingerprint→signatures→search→filter chain is deleted, its output
golden-pinned bit-exact against the replay
(``tests/golden/batch_detect.json``). Every guard below is therefore
available to archive reprocessing through the same ``StreamConfig``
knobs, and any future guard or kernel lands in one place and serves
both drivers. ``detect_step`` (the dry-run cell) wraps the same
``guarded_step`` tail over a fresh in-trace index.

Future PRs must not re-split this step: anything added to the per-block
path (new filters, extra statistics) belongs *inside* the traced program
or strictly on the host side of the pair stream. The retracing guard
(≤1 trace across same-shape chunks), the donation guard (flat
``jax.live_arrays`` across steady-state chunks), and the fused-vs-unfused
parity test in ``tests/test_stream.py`` enforce the invariant; the
unfused chain (``block_coeffs`` + ``stream_step``, ``fused=False``) stays
as the bit-exact reference.

Data-quality path (ISSUE 4)
---------------------------

Real telemetry is pathological — gaps, out-of-order and duplicated
chunks, repeating instrument glitches — and every defense lives either
*before* the dispatch or *inside* the one traced program, never as an
extra dispatch:

* **gap-aware ingest** (``WaveformRing``): ``push(chunk, offset)``
  places chunks on the absolute sample timeline; NaN samples and offset
  jumps become sentinel-filled invalid spans, and each emitted block
  carries a per-fingerprint validity mask (a fingerprint is valid iff
  its whole window holds real samples). Masked blocks route through the
  already-traced ``step_block`` — suppressed fingerprints get filler
  signatures in-dispatch, are never inserted or queried, and the
  donation/retracing invariants are untouched (pinned by
  ``test_quality_path_single_dispatch_invariants``).
* **reorder reconciliation** (``StreamConfig.reorder_horizon_samples``):
  block emission is held back by the horizon so late chunks splice into
  place and duplicated deliveries drop deterministically (first writer
  wins); permutation-invariance within the horizon is a pinned property.
* **repeated-segment guard** (``dup_window_fingerprints``): sample-exact
  window hashes flag telemetry-duplicated blocks and flat-lined channels
  *before* the dispatch — structurally zero false positives on clean
  data (continuous noise never repeats bit-exactly).
* **bucket-saturation quarantine** (``saturation_limit``, in-dispatch):
  buckets whose insert-traffic counter exceeds the limit stop emitting
  pairs — the paper's repeating-glitch mega-bucket fix. With a sliding
  window the counter is *window-relative*: it halves once per window
  inside the traced ``expire`` (``IndexState.traffic`` — separate from
  the monotonic ring ``cursor``), so quarantined buckets recover once a
  glitching channel is repaired and the guard is safe on unbounded
  multi-month streams. The signature-level ``dup_sig_tables`` guard is
  the aggressive per-deployment variant (strong legitimate repeaters can
  collide in all tables).
* **in-dispatch §6.5 occurrence limiter** (``occ_limit``, ISSUE 5): raw
  partner collisions — (table, slot) signature matches at id distance ≥
  ``min_dt``, the §6.3 lookups-per-query skew signal — are accumulated
  per fingerprint in an id-keyed ring (``IndexState.occ``, slots
  recycled as the window slides), and pairs touching a fingerprint past
  the limit are dropped inside the same traced program. This is what
  suppresses *additive* (non-sample-exact) glitch trains ≥10× — they
  ride the live noise floor, so the duplicate guard cannot see them and
  the saturation quarantine alone only managed ~2×. The host-side
  ``occurrence_filter`` (one shared invocation,
  ``engine.host_occurrence_filter``, used by finalize and the batch
  replay driver; the rolling filter runs its numpy twin
  ``lsh.occurrence_keep``) remains the bit-exact §6.5
  reference/fallback.

With every knob at its default (off) — and on clean data even with the
knobs on — the traced program and the emitted pair set are bit-identical
to the unguarded path (``test_quality_path_clean_bit_parity``). The
scenario generator (``core.synth.make_scenario_dataset``) is the shared
fault-injection substrate for ``tests/test_scenarios.py`` and
``bench_stream --scenario`` (spurious-pair suppression recorded in
``BENCH_stream.json``); reconciliation and guard counters surface
through ``StreamingDetector.quality_summary`` and ``serve_detect``.

Observability path (ISSUE 6)
----------------------------

Telemetry follows the same discipline as the quality path: everything is
either *inside* the already-traced program or on the host side of the
pair stream — never an extra dispatch, never a change to detections.

* **in-dispatch counters** (``index.QC_FIELDS``): every fused/unfused
  step returns a per-station counter vector computed inside the traced
  program — pairs emitted, fingerprints masked by validity, raw and
  quarantined collisions, duplicate-suppressed fingerprints, limiter
  drops. The guard counters are always live; the telemetry-only entries
  are gated by the static ``StreamConfig.telemetry`` knob (default on)
  and constant-fold to zero when off, so telemetry-off compiles the
  exact pre-ISSUE-6 program and telemetry-on stays one dispatch with
  bit-identical detections (both pinned in ``tests/test_telemetry.py``).
  Counters only *read* the guard masks; they never feed back into pairs.
* **host metrics registry** (``repro.obsv.metrics``): labeled counters,
  gauges, and log-bucketed histograms (chunk-ingest / fused-dispatch /
  host-tail walls, ``host_state_rows``, ring reorder+gap counters) with
  O(1) memory per series; snapshots/restores alongside the detector so a
  restarted service resumes its counters. Rendered as Prometheus text
  exposition (``repro.obsv.metrics.render_prometheus``).
* **span tracing** (``repro.obsv.spans.SpanTracer``): one span tree per
  push — ``chunk`` → ``ingest`` (ring framing, block staging; ``dedup``,
  the duplicate guard, inside it), ``fused_step`` (``put``,
  ``dispatch``, ``wait``, ``pull``), ``host_tail``, ``detections`` —
  each layer timed where its work happens, each span wrapping the loop
  over stations. Records carry ``id``/``parent``/``trace`` and
  ``CLOCK_REALTIME`` ``start_ns``/``end_ns``, buffered in memory and
  written as JSONL on flush; every span is also a
  ``jax.profiler.TraceAnnotation``, so a profile of the running service
  shows the host stage beside the device's operations on one clock. The
  registry histograms and the watchdog read the span durations; the
  batch replay's ``core.detect.StageTimes`` is *derived* from the span
  totals. On the device, the step's stages run under
  ``jax.named_scope`` (``fingerprint``, ``hash``, ``expire``,
  ``dup_guard``, ``insert``, ``query``, ``limit``, ``compact``,
  ``verify``), carried in every operation's metadata.
* **watchdog**: the training loop's ``train/watchdog.StepWatchdog``
  observes every streaming dispatch — one step per pooled dispatch, the
  ``fused_step`` span's duration — flagging stragglers into
  ``straggler_steps_total``.
* **health surface**: ``StreamingDetector.metrics_snapshot()`` is the
  single structured view (schema ``stream-metrics/v1``) consumed by
  ``bench_stream`` / ``bench_e2e`` artifacts, the examples, and
  ``serve_detect --metrics-every/--metrics-file`` (heartbeat JSON lines
  with real-time factor + per-guard drop rates; atomically rewritten
  Prometheus exposition). The hub tying these together is
  ``stream.telemetry.StreamTelemetry`` (one per detector, shared by its
  stations).

Serving tier (ISSUE 7)
----------------------

``launch/serve_detect.py`` grows the slot/refill idiom into a
concurrent, backpressured query service over the index pool; the flow
per request is **admission queue → batched ``_serve_step`` → refresh
cadence → shed path**:

* **admission** (``ServeDetectEngine.submit``): a bounded FIFO in front
  of the slots. Depth past ``max_queue`` load-sheds — the request
  completes immediately with ``outcome="rejected"`` (the overload
  contract: answer *something* fast instead of queueing without bound;
  a burst of B > max_queue sheds exactly B − max_queue, pinned by
  ``tests/test_serve.py``). Every request carries arrival-time
  accounting: queue wait (submit → slot) and service time (slot →
  done) are split in the latency records.
* **batched ticks** (``ServeDetectEngine.tick``): each tick admits
  queued requests into free slots and runs **one** jitted dispatch that
  fingerprints all active slots once and queries every station's index
  read-only — concurrent requests share device dispatches exactly like
  decode slots share a decode step, and the answers are pinned
  identical to sequential single-slot serving. Idle ticks (no active
  slots) return without assembling a batch or dispatching.
* **refresh cadence** (``refresh_from`` / ``ServeSession``): serving
  runs against a *copied* ``pool_serving_state()`` snapshot (donation
  safety), refreshed at a configured chunk cadence and gated on
  ``StreamingDetector.serving_version`` so an unchanged corpus costs
  nothing. ``ServeSession`` is the cooperative single-thread loop —
  ingest chunks keep growing the pool while query ticks run between
  them (``ingest_chunks(..., on_chunk=...)``), so the corpus grows
  under live queries (``serve_detect --interleave``).
* **telemetry**: the engine publishes through the shared PR-6 registry
  (``serve_requests_total{outcome}``, queue-depth/slot-occupancy
  gauges, queue-wait/service/latency histograms,
  ``serve_state_refreshes_total``), surfaced in the heartbeat,
  the Prometheus exposition, and ``metrics_snapshot()["serve"]``;
  ``benchmarks/bench_serve.py`` records sustained QPS, the p50/p99
  latency split, and shed rates under closed-loop concurrent clients
  (``BENCH_serve.json``).

Snapshots (``--snapshot-every``), restart (``--restore``, which grows
the restored pool elastically when ``--stations`` exceeds the snapshot
width — ISSUE 10 — and rejects shrinks, which would discard station
identities), and the live
health surface (``--metrics-every``, ``--metrics-file``,
``--trace-jsonl``, ``--dirty``) ride the same CLI.

Emission path (ISSUE 8)
-----------------------

The dense pair emission is O(t · N · cap) slots per block — at the paper
configuration (t=100, cap=8) that is ~205k candidate slots per station
per 256-fingerprint block, nearly all invalid, every one transferred to
the host and scanned there. Two in-dispatch epilogue stages shrink the
pipe to O(max_pairs):

* **compaction** (``index.compact_pairs``, ``max_pairs_per_block`` > 0):
  after the m-of-t reduction, surviving pairs are gathered into a
  bounded static-shape ``(max_pairs,)`` buffer via a ``top_k`` over
  stream position — deterministic (first ``max_pairs`` valid positions
  = lexicographically smallest (idx1, idx2) survive; re-running a block
  drops the *same* pairs), donation-safe, and counted: overflow drops
  land in the ``overflow_pairs`` slot of ``QC_FIELDS`` and surface
  through ``drop_breakdown()`` / ``step_overflow_pairs_total``.
* **exact-Jaccard verify** (``index.verify_pairs``,
  ``verify_jaccard``): the binarizer's bit-packed fingerprints are
  stashed in a window-sized device ring (``IndexState.pk``, keyed by
  id % pk_slots, carried through snapshot/restore) and every compacted
  candidate is scored with exact Jaccard via
  ``kernels.jaccard_popcount`` — the jnp oracle, or the Pallas popcount
  kernel with ``verify_pallas`` (interpret-mode parity pinned in
  ``tests/test_kernels.py``). Pairs then emit as
  ``core.lsh.VerifiedPairs`` (idx1, idx2, hash matches, jaccard), and
  ``verify_min_jaccard`` drops false LSH collisions in-dispatch so
  downstream thresholds act on true similarity, not the hash proxy.

Both stages run inside the same traced program (one dispatch, donated
buffers), in every driver — solo and pooled streaming, batch replay,
and the serving tier's read-only slot queries. With the knobs at 0 the
dense emission and the traced program are exactly as before; with
compaction sized above the true pair rate the emitted pair set is
bit-identical to dense (golden-pinned). ``benchmarks/bench_e2e.py``
records the A/B (``emission`` section: pair bytes per block, device-
step vs host-tail wall split) and ``make bench-emit`` refreshes it.

Association → location → magnitude (ISSUE 9)
--------------------------------------------

Detection ends the paper's pipeline at "same (dt, onset±tol) at ≥2
stations" (§7, Figure 9) — a detection is a *coincidence*, with no
place, no size, and no defense against cross-station coincidences that
fit no physical moveout. The location tier (``core/locate.py``) turns
each associated group into a located, weighted, sized detection, in
three host-side stages downstream of the pair stream (never an extra
per-block dispatch):

* **association** (``core.align.associate_network(..., with_onsets)``):
  the §7 grouping, with station multiplicity counted through packed
  int32 bitmask words (no 32-station cap) and, when the locate tier is
  on, per-group ``(p, S)`` station-onset / station-score matrices —
  each present station's earliest onset and Jaccard-weighted mass.
* **location** (``locate.locate_groups``): a coarse-to-fine migration
  stack — candidate origins on a ``grid_n²`` surface grid, per-station
  travel-time moveouts subtracted from the onset matrix, the weighted
  t0/residual evaluated everywhere at once (jit + vmap over groups),
  argmin refined ``refine_levels`` times. The weighted mean absolute
  residual doubles as the **moveout-consistency gate**: a group whose
  onsets fit no candidate origin within ``moveout_tol_lags`` is a
  cross-station coincidence and (``reject_inconsistent``) is dropped —
  discriminative from 3 stations up (two stations always fit). Station
  weights come from the PR-4/PR-6 QC counters
  (``locate.station_weights``): dirty stations pull the stack less,
  dead ones are floored at ``min_weight``, never zero.
* **magnitude** (``locate.relative_magnitude``): per detection, the
  weighted median over stations of ``log10`` peak-amplitude ratios
  between the re-occurrence and the first occurrence — batch reads
  whole-trace per-fingerprint peaks (``locate.fingerprint_amplitudes``),
  streaming keeps a bounded per-station lag-bin amplitude timeline
  pruned with the association floor; both feed the same
  ``locate.attach_location`` stage via an ``amp_fn`` closure.

Both drivers share the stage: batch ``detect_events(station_xy=...)``
appends it after association, and the streaming detector runs it in
``poll_detections`` (alerts grow upgrade/x/y/magnitude columns — an
alert re-emits flagged when a late station upgrades its multiplicity)
and ``finalize``. Telemetry rides the PR-6 registry
(``locate_view()``: passes, located, moveout-rejected, stack-wall
histogram); ``bench_stream --assoc`` records the A/B where the moveout
gate cuts ≥3-station false associations under shared-period noise
pressure while keeping every true group (``BENCH_stream.json``,
``located_scenario`` key; ``make bench-assoc`` refreshes it).

Sharded station pool (ISSUE 10)
-------------------------------

The pooled hot path stacks every station's ``FusedState`` on a leading S
axis; sharding splits that axis across a 1-axis ``stations`` device mesh
(``dist.station_mesh``) so the network's ceiling is the fleet, not one
chip. Three properties make this the cheap kind of distribution:

* **zero in-region collectives**: stations are independent until the
  host-side association tail, so ``pool_step_*_sharded`` run the same
  per-station ``core`` under ``jax.shard_map`` **fully manual** over
  the ``stations`` axis — no cross-device communication inside the
  traced program. Donation
  and the one-dispatch-per-block invariant carry over per shard; the
  pair/QC outputs come back through the same single ``device_get``.
* **capability probe, vmap fallback**: ``dist.station_mesh`` returns
  ``None`` on one visible device or fewer than two stations, and the
  sharded entries then delegate to the bit-identical ``vmap`` pool —
  ``StreamConfig.sharded`` (default on) is inert on a laptop and a
  no-code-change scale-out on a multi-device host. When S does not
  divide the mesh, the pool pads with throwaway station clones (row-
  independent math; outputs never read) rather than idling devices.
* **mesh-elastic state**: snapshots store per-station slices (device
  topology never reaches disk), so a pool saved under 8 devices
  restores onto 1 or 4 unchanged — and the live pool is elastic too:
  ``StreamingDetector.add_station`` / ``remove_station`` re-pad and
  re-shard the stacked pytree mid-stream (the joiner mirrors a peer's
  ring framing with its pre-join span masked missing, so lockstep block
  emission holds from the first post-join block).

``benchmarks/bench_e2e.py`` records the device-count × stations scaling
grid (``sharded_pool`` section, ``make bench-sharded``) under
``--xla_force_host_platform_device_count``, with exact step percentiles
and per-point sharded-vs-vmap pair parity; forced host devices time-
slice the physical cores, so the recorded speedup only reads as a
scaling curve when ``host_cores`` ≥ the device count.

Unbounded streams run *bounded*: with ``StreamConfig.window_fingerprints``
the jitted step expires index entries beyond a sliding detection window,
and with ``filter_window_fingerprints`` each station's
``RollingPairFilter`` retires candidate pairs window-by-window through
the §6.5 occurrence filter (per component) and the channel merge of the
station's components into compact event rows — O(window) host state,
near-real-time multi-station alerts via
``StreamingDetector.poll_detections``, and exact kill/restore via
``snapshot``/``restore`` (checkpointed through ``train/checkpoint``).

A parity test (tests/test_stream.py) holds the streamed path to ≥95% of
the offline ``lsh.search`` pair set on synthetic traces; a golden test
(tests/golden/) pins the exact streamed pair set against drift.
"""
from repro.stream.engine import (RollingPairFilter,  # noqa: F401
                                 StationStream, StreamingDetector,
                                 StreamStats, block_coeffs, ingest_chunks,
                                 events_from_rows, events_to_rows,
                                 finalize_station,
                                 host_occurrence_filter,
                                 merge_boundary_rows, pairs_from_triplets,
                                 pool_block_coeffs, stream_step)
from repro.stream.fused import (FusedState, init_pool_state,  # noqa: F401
                                init_state, pool_step_advance,
                                pool_step_advance_sharded, pool_step_block,
                                pool_step_block_sharded, step_advance,
                                step_block)
from repro.stream.index import (IndexState, QC_FIELDS,  # noqa: F401
                                StreamIndexConfig, compact_pairs, expire,
                                index_stats, init_index, init_pool, insert,
                                query, slice_state, stack_states,
                                verify_pairs)
from repro.stream.ingest import (StreamConfig, StreamingMAD,  # noqa: F401
                                 WaveformRing)
from repro.stream.telemetry import (METRICS_SCHEMA,  # noqa: F401
                                    StreamTelemetry, metrics_snapshot)
