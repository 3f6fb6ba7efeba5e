"""Device-resident incremental LSH index (streaming replacement for §6).

The offline search re-sorts every signature on every run; here the hash
tables are *materialized* as fixed-capacity bucket arrays that live on
device across chunks:

  ``sig[t, B, C]``  stored per-table signature of each slot (uint32)
  ``ids[t, B, C]``  global fingerprint id of each slot (INVALID = empty)
  ``cursor[t, B]``  per-bucket ring write position (monotonic)

``insert`` scatters a batch of signatures into their buckets — within a
batch, same-bucket rows get consecutive ring positions via a sort +
rank-in-run, so a bucket overflowing its capacity ``C`` evicts its oldest
entries (the paper's mega-bucket pathology is therefore *structurally*
capped, like ``bucket_cap`` in the offline sort-based search). ``query``
gathers each signature's bucket occupants, keeps exact-signature hits, and
feeds the per-table emission streams through the same
``finalize_pairs`` (min_dt self-match exclusion + m-of-t threshold) as the
batch path — one implementation of the pair semantics, two search engines.

Both ops are jitted with static shapes: chunk after chunk of the same
batch size reuses one executable (no retracing), which is what makes the
incremental path O(batch) instead of O(corpus).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lsh as lsh_mod
from repro.core.lsh import (INVALID, LSHConfig, Pairs, VerifiedPairs,
                            finalize_pairs)
from repro.kernels import ops
from repro.utils import rank_in_run, run_lengths

# Layout of the per-step quality/telemetry counter vector returned by
# ``guarded_step`` (and therefore by every fused step entry). The first
# three are the ISSUE-4/5 guard counters and are always live; the rest
# are the ISSUE-6 telemetry extension, computed inside the same traced
# program when ``counters`` is set and constant-folded to 0 otherwise.
QC_FIELDS = (
    "duplicate_fingerprints",    # fingerprints suppressed by the dup probe
    "saturated_lookups",         # valid lookups landing in hot buckets
    "limited_pairs",             # pairs dropped by the §6.5 occ ring
    "pairs_emitted",             # finalized valid pairs leaving the step
    "masked_fingerprints",       # fingerprints suppressed by the validity
                                 # mask (gaps / dup samples / flush tails)
    "raw_collisions",            # (table, slot) sig matches pre-guard —
                                 # the §6.3 lookups-per-query skew signal
    "quarantined_collisions",    # raw collisions killed by the bucket-
                                 # saturation quarantine
    "overflow_pairs",            # valid pairs dropped by the emission
                                 # compaction bound (ISSUE 8; 0 when the
                                 # compacted buffer fit every pair)
)


@dataclasses.dataclass(frozen=True)
class StreamIndexConfig:
    """Shape of the resident index (capacity knobs, not semantics)."""

    n_buckets: int = 4096     # buckets per table (power of two)
    bucket_cap: int = 8       # slots per bucket (ring, oldest evicted)
    occ_slots: int = 0        # per-fingerprint partner-count ring (ISSUE 5:
                              # the in-dispatch §6.5 limiter; 0 = no ring)
    pk_slots: int = 0         # bit-packed fingerprint ring rows (ISSUE 8:
                              # the in-dispatch verify epilogue; 0 = none)
    pk_words: int = 0         # uint32 words per packed row (fp_dim // 32;
                              # 0 lets the engine derive it from the
                              # fingerprint config)

    def __post_init__(self):
        assert self.n_buckets & (self.n_buckets - 1) == 0, \
            f"n_buckets must be a power of two, got {self.n_buckets}"
        assert self.occ_slots >= 0, self.occ_slots
        assert self.pk_slots >= 0, self.pk_slots
        assert self.pk_words >= 0, self.pk_words

    def state_bytes(self, n_tables: int) -> int:
        slots = n_tables * self.n_buckets * self.bucket_cap
        return (slots * (4 + 4) + 2 * n_tables * self.n_buckets * 4
                + max(self.occ_slots, 1) * 4
                + max(self.pk_slots, 1) * max(self.pk_words, 1) * 4)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class IndexState:
    sig: jax.Array      # (t, B, C) uint32
    ids: jax.Array      # (t, B, C) int32, INVALID where empty
    cursor: jax.Array   # (t, B) int32 monotonic ring cursor
    inserted: jax.Array  # () int32 total rows ever inserted
    traffic: jax.Array  # (t, B) int32 bucket insert traffic; unlike
                        # ``cursor`` (the ring write position, which must
                        # stay monotonic) it DECAYS under a sliding window
                        # so the saturation quarantine is window-relative
    occ: jax.Array      # (L,) int32 per-fingerprint emitted-partner counts
                        # (ring keyed by id % L; L = occ_slots or 1)
    epoch: jax.Array    # () int32 last traffic-decay epoch (expire)
    pk: jax.Array       # (P, W) uint32 bit-packed fingerprint ring keyed
                        # by id % P (ISSUE 8 verify; P = pk_slots or 1)

    @property
    def shape(self) -> tuple[int, int, int]:
        return self.sig.shape


def init_index(lcfg: LSHConfig, icfg: StreamIndexConfig) -> IndexState:
    t, b, c = lcfg.n_tables, icfg.n_buckets, icfg.bucket_cap
    return IndexState(
        sig=jnp.zeros((t, b, c), jnp.uint32),
        ids=jnp.full((t, b, c), INVALID, jnp.int32),
        cursor=jnp.zeros((t, b), jnp.int32),
        inserted=jnp.zeros((), jnp.int32),
        traffic=jnp.zeros((t, b), jnp.int32),
        occ=jnp.zeros((max(icfg.occ_slots, 1),), jnp.int32),
        epoch=jnp.zeros((), jnp.int32),
        pk=jnp.zeros((max(icfg.pk_slots, 1), max(icfg.pk_words, 1)),
                     jnp.uint32),
    )


# bucket addressing lives in core/lsh.py (shared with the fused kernel
# epilogue); kept as a local alias for callers of the old private name
_bucket_ids = lsh_mod.bucket_ids


def _insert_one_table(sig_tb, ids_tb, cursor_tb, traffic_tb, buckets, keys,
                      new_ids, valid):
    """Scatter one batch into one table's (B, C) bucket arrays.

    The scatter indexes (bucket row, ring position) on the (B, C) arrays
    as they are: a flat ``B * C`` view would need C minor, which the TPU
    pads from 8 to 128 lanes, relaying out the whole table twice a step.
    """
    b, c = sig_tb.shape
    n = buckets.shape[0]
    order_key = jnp.where(valid, buckets, jnp.int32(b))  # invalid rows last
    sb, perm = jax.lax.sort((order_key, jnp.arange(n, dtype=jnp.int32)),
                            num_keys=1)
    rank = rank_in_run(sb)
    _, lens = run_lengths(sb)
    keep = (sb < b) & (rank >= lens - c)   # newest C of each bucket run
    pos = (cursor_tb[jnp.where(sb < b, sb, 0)] + rank) % c
    row = jnp.where(keep, sb, b)           # OOB row → dropped
    k_s = keys[perm]
    id_s = new_ids[perm]
    new_sig = sig_tb.at[row, pos].set(k_s, mode="drop")
    new_ids_tb = ids_tb.at[row, pos].set(id_s, mode="drop")
    # advance cursors by the full run length (ring continues past drops);
    # the traffic counter advances identically but may later decay
    adds = valid.astype(jnp.int32)
    new_cursor = cursor_tb.at[buckets].add(adds, mode="drop")
    new_traffic = traffic_tb.at[buckets].add(adds, mode="drop")
    return new_sig, new_ids_tb, new_cursor, new_traffic


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(0,))
def insert(state: IndexState, sigs: jax.Array, ids: jax.Array,
           cfg: LSHConfig, valid: jax.Array | None = None,
           buckets: jax.Array | None = None) -> IndexState:
    """Insert a batch of per-table signatures under global fingerprint ids.

    sigs: (N, t) uint32; ids: (N,) int32 (monotone across the stream).
    Fixed shapes — one trace per (N, index shape) combination.
    ``buckets`` (N, t) skips bucket addressing when the caller already has
    it (the fused chunk step computes it once for insert *and* query).
    """
    t, b, c = state.shape
    n = sigs.shape[0]
    if valid is None:
        valid = jnp.ones((n,), bool)
    if buckets is None:
        buckets = lsh_mod.bucket_ids(sigs, b, cfg.seed)   # (N, t)
    new_sig, new_ids, new_cursor, new_traffic = jax.vmap(
        _insert_one_table, in_axes=(0, 0, 0, 0, 1, 1, None, None))(
        state.sig, state.ids, state.cursor, state.traffic, buckets,
        sigs.astype(jnp.uint32), ids, valid)
    return IndexState(sig=new_sig, ids=new_ids, cursor=new_cursor,
                      inserted=state.inserted + valid.sum(dtype=jnp.int32),
                      traffic=new_traffic, occ=state.occ, epoch=state.epoch,
                      pk=state.pk)


@functools.partial(jax.jit, static_argnames=("cfg", "saturation", "counts",
                                              "max_pairs"))
def query(state: IndexState, sigs: jax.Array, qids: jax.Array,
          cfg: LSHConfig, buckets: jax.Array | None = None,
          qvalid: jax.Array | None = None, saturation: int = 0,
          counts: int = 0, max_pairs: int = 0):
    """Find stored partners of a signature batch → thresholded Pairs.

    Only partners with stored id < query id are emitted, so a batch that
    was just inserted pairs exactly once with every earlier fingerprint
    (including same-batch ones) per colliding table — the streaming
    equivalent of the offline rank-window emission. Returns a masked
    ``Pairs`` of static size t * N * C.

    ``qvalid`` suppresses emission for flagged query rows (duplicate-
    guarded fingerprints keep their real signatures but must not pair).
    ``saturation`` > 0 quarantines saturated buckets from emission: hits
    inside a bucket whose insert-traffic counter exceeds the limit are
    dropped — the repeating-glitch mega-bucket fix. The counter is
    ``state.traffic``, which a sliding window decays (see ``expire``), so
    quarantined buckets recover once the offending channel is repaired.
    Both default off, leaving the traced program unchanged.

    ``counts`` (static, telemetry) additionally returns
    ``(pairs, [raw_collisions, quarantined_collisions])`` — the pre-guard
    (table, slot) signature-match total (the §6.3 lookups-per-query skew
    signal; dup-suppressed rows keep their real signatures so their
    collisions are intentionally included) and the subset of it killed by
    the saturation quarantine. Two reductions over masks the program
    already materializes — no new dispatch, pair outputs untouched.

    ``max_pairs`` (static, ISSUE 8) > 0 compacts the dense emission
    through :func:`compact_pairs` so the returned ``Pairs`` has static
    size ``max_pairs`` instead of t * N * C — the O(P) shape serving-tier
    callers reduce over. Overflow past the bound drops deterministically
    (see ``compact_pairs``); callers needing the overflow count use
    ``guarded_step``, which also appends it to the QC vector.
    """
    t, b, c = state.shape
    n = sigs.shape[0]
    if buckets is None:
        buckets = lsh_mod.bucket_ids(sigs, b, cfg.seed)   # (N, t)

    def one_table(sig_tb, ids_tb, cur_tb, bkt, keys):
        occ_sig = sig_tb[bkt]                          # (N, C)
        occ_id = ids_tb[bkt]                           # (N, C)
        raw = (occ_sig == keys[:, None]) & (occ_id != INVALID) \
            & (occ_id < qids[:, None])
        hit = raw
        n_quar = jnp.int32(0)
        if saturation > 0:
            ok = (cur_tb[bkt] <= jnp.int32(saturation))[:, None]
            hit = hit & ok
            if counts:
                n_quar = (raw & ~ok).sum(dtype=jnp.int32)
        if qvalid is not None:
            hit = hit & qvalid[:, None]
        lo = jnp.where(hit, occ_id, INVALID)
        hi = jnp.where(hit, qids[:, None], INVALID)
        n_raw = raw.sum(dtype=jnp.int32) if counts else jnp.int32(0)
        return lo, hi, n_raw, n_quar

    lo, hi, n_raw, n_quar = jax.vmap(one_table, in_axes=(0, 0, 0, 1, 1))(
        state.sig, state.ids, state.traffic, buckets,
        sigs.astype(jnp.uint32))
    pairs = finalize_pairs(lo.reshape(-1), hi.reshape(-1), cfg)
    if max_pairs > 0:
        pairs, _ = compact_pairs(pairs, max_pairs)
    if not counts:
        return pairs
    return pairs, jnp.stack([n_raw.sum(), n_quar.sum()])


@functools.partial(jax.jit, static_argnames=("half_life",))
def expire(state: IndexState, min_id: jax.Array,
           half_life: int = 0) -> IndexState:
    """Sliding detection window: drop entries with id < min_id.

    ``half_life`` > 0 additionally makes the bucket-saturation traffic
    counter *window-relative*: every time ``min_id`` crosses a half-life
    boundary the counter is halved (a right shift per crossed epoch), so
    ``traffic`` approximates recent-window insert pressure instead of
    lifetime totals and quarantined buckets recover once a glitching
    channel is repaired. 0 keeps the lifetime counter (and the exact
    pre-decay traced program).
    """
    keep = state.ids >= jnp.int32(min_id)
    traffic, epoch = state.traffic, state.epoch
    if half_life > 0:
        new_epoch = jnp.maximum(jnp.asarray(min_id, jnp.int32), 0) \
            // jnp.int32(half_life)
        shift = jnp.clip(new_epoch - epoch, 0, 31)
        traffic = traffic >> shift          # halve once per crossed epoch
        epoch = new_epoch
    return IndexState(sig=state.sig,
                      ids=jnp.where(keep, state.ids, INVALID),
                      cursor=state.cursor, inserted=state.inserted,
                      traffic=traffic, occ=state.occ, epoch=epoch,
                      pk=state.pk)


# ---------------------------------------------------------------------------
# degenerate-similarity guards (ISSUE 4): duplicate probe + saturation
# ---------------------------------------------------------------------------


def duplicate_flags(state: IndexState, sigs: jax.Array, ids: jax.Array,
                    cfg: LSHConfig, dup_tables: int,
                    buckets: jax.Array | None = None,
                    valid: jax.Array | None = None) -> jax.Array:
    """(N,) bool — near-exact repeated segments, flagged *before* insert.

    A fingerprint is a repeat when its per-table signatures collide with
    resident index entries (or earlier rows of the same batch) in at
    least ``dup_tables`` of the t tables, at id distance ≥ ``min_dt``.
    Bit-exact duplicated data blocks collide in all t tables; repeating
    glitches in nearly all; genuine repeating earthquakes (differing
    noise floors) in only a few — a threshold near t separates artifact
    from signal. Traced inline by the fused step (no extra dispatch).
    """
    t, b, c = state.shape
    if buckets is None:
        buckets = lsh_mod.bucket_ids(sigs, b, cfg.seed)
    keys = sigs.astype(jnp.uint32)
    far = ids[:, None] - jnp.int32(max(cfg.min_dt, 1))

    def one_table(sig_tb, ids_tb, bkt, k):
        occ_sig = sig_tb[bkt]                          # (N, C)
        occ_id = ids_tb[bkt]
        hit = ((occ_sig == k[:, None]) & (occ_id != INVALID)
               & (occ_id <= far))
        return hit.any(axis=1)                         # (N,)

    resident = jax.vmap(one_table, in_axes=(0, 0, 1, 1))(
        state.sig, state.ids, buckets, keys).sum(axis=0)    # (N,)
    # earlier rows of this batch (they are not yet resident)
    same = (keys[:, None, :] == keys[None, :, :]).sum(-1)   # (N, N)
    earlier = ids[None, :] <= far
    if valid is not None:
        earlier = earlier & valid[None, :]
    intra = jnp.where(earlier, same, 0).max(axis=1)
    dup = jnp.maximum(resident, intra) >= jnp.int32(dup_tables)
    if valid is not None:
        dup = dup & valid
    return dup


def saturated_lookup_count(state: IndexState, buckets: jax.Array,
                           saturation: int,
                           valid: jax.Array | None = None) -> jax.Array:
    """How many of this batch's valid (row, table) lookups landed in a
    quarantined bucket — the saturation monitoring counter. Invalid rows
    carry pseudo-random filler buckets and must not pollute the count."""
    cur = jax.vmap(lambda c, b: c[b], in_axes=(0, 1))(
        state.traffic, buckets)                        # (t, N)
    hot = cur > jnp.int32(saturation)
    if valid is not None:
        hot = hot & valid[None, :]
    return hot.sum(dtype=jnp.int32)


def occurrence_limit_pairs(state: IndexState, sigs: jax.Array,
                           buckets: jax.Array, ids: jax.Array,
                           qvalid: jax.Array | None, cfg: LSHConfig,
                           pairs: Pairs, limit: int
                           ) -> tuple[IndexState, Pairs, jax.Array]:
    """In-dispatch §6.5 occurrence limiter (ISSUE 5 tentpole).

    Counts every raw partner collision — a (table, slot) signature match
    at id distance ≥ ``min_dt``, the §6.3 lookups-per-query skew signal,
    *before* any ring-cap / threshold / quarantine attenuation — against
    both endpoints' per-fingerprint counters in the ``occ`` ring (keyed
    by id % L; slots recycle as the window slides, so counts are
    window-relative like the host filter's per-partition fractions).
    Pairs touching a fingerprint whose accumulated count exceeds
    ``limit`` are then dropped *inside the already-traced program*. A
    repeating glitch train collides with its ring-resident siblings in
    nearly every table, so its fingerprints blow past the limit within
    their first block and the train's pairs — including additive,
    non-sample-exact trains the duplicate guard cannot see — die
    in-dispatch; a legitimate repeater's lifetime total stays near the
    sum of its pair similarities, far below a sanely sized limit, so
    clean data is bit-identical with the limiter on or off (pinned).
    The host-side ``occurrence_filter`` stays as the exact §6.5
    reference/fallback. Returns (state, limited pairs, pairs dropped).
    """
    t, b, c = state.shape
    ring = state.occ.shape[0]
    keys = sigs.astype(jnp.uint32)
    far = ids[:, None] - jnp.int32(max(cfg.min_dt, 1) - 1)  # id dist ≥ min_dt

    def one_table(sig_tb, ids_tb, bkt, k):
        occ_sig = sig_tb[bkt]                          # (N, C)
        occ_id = ids_tb[bkt]
        hit = ((occ_sig == k[:, None]) & (occ_id != INVALID)
               & (occ_id < far))
        if qvalid is not None:
            hit = hit & qvalid[:, None]
        return hit, occ_id

    hit, occ_id = jax.vmap(one_table, in_axes=(0, 0, 1, 1))(
        state.sig, state.ids, buckets, keys)           # (t, N, C) each
    q_counts = hit.sum(axis=(0, 2), dtype=jnp.int32)   # (N,)
    pslot = jnp.where(hit, occ_id % ring, ring).reshape(-1)  # OOB → dropped
    occ = state.occ.at[ids % ring].add(q_counts, mode="drop") \
        .at[pslot].add(hit.reshape(-1).astype(jnp.int32), mode="drop")
    hot = occ > jnp.int32(limit)
    v = pairs.valid
    s1 = jnp.where(v, pairs.idx1 % ring, 0)
    s2 = jnp.where(v, pairs.idx2 % ring, 0)
    keep = v & ~hot[s1] & ~hot[s2]
    dropped = (v & ~keep).sum(dtype=jnp.int32)
    limited = Pairs(idx1=pairs.idx1, idx2=pairs.idx2,
                    sim=jnp.where(keep, pairs.sim, 0), valid=keep)
    return dataclasses.replace(state, occ=occ), limited, dropped


# ---------------------------------------------------------------------------
# emission epilogue (ISSUE 8): compaction + exact-Jaccard verify
# ---------------------------------------------------------------------------


def compact_pairs(pairs: Pairs, max_pairs: int
                  ) -> tuple[Pairs, jax.Array]:
    """Validity compaction of the dense emission stream (ISSUE 8).

    The dense stream leaving ``finalize_pairs`` is t * N * C slots,
    almost all masked; this gathers the surviving pairs into a bounded
    ``(max_pairs,)`` buffer so only real pairs cross the device→host
    boundary. The drop rule on overflow is deterministic: the stream is
    (idx1, idx2)-sorted (valid pairs sit at segment starts of the
    ``lax.sort`` in ``count_pair_multiplicity``), and the compaction
    keeps the *first* ``max_pairs`` valid positions — i.e. the
    lexicographically smallest (idx1, idx2) pairs — independent of
    backend reduction order. Returns (compacted pairs, overflow count).
    """
    m = pairs.valid.shape[0]
    k = min(max_pairs, m)
    pos = jnp.arange(m, dtype=jnp.int32)
    # valid rows outrank invalid ones; within each class earlier stream
    # positions outrank later ones, so top_k takes the first k valid
    # positions (padding from the stream head when fewer are valid)
    score = jnp.where(pairs.valid, 2 * m - pos, m - pos)
    _, take = jax.lax.top_k(score, k)
    kept = pairs.valid[take]
    overflow = (pairs.valid.sum(dtype=jnp.int32)
                - kept.sum(dtype=jnp.int32))
    return Pairs(idx1=pairs.idx1[take], idx2=pairs.idx2[take],
                 sim=pairs.sim[take], valid=kept), overflow


def verify_pairs(state: IndexState, pairs: Pairs,
                 use_pallas: bool = False) -> jax.Array:
    """Exact Jaccard of compacted candidates from the packed ring.

    Gathers both endpoints' bit-packed fingerprints out of the
    ``IndexState.pk`` ring (keyed by id % pk_slots — valid as long as the
    ring spans the detection window, which config validation enforces)
    and scores them with ``kernels.jaccard_popcount`` (the jnp oracle, or
    the interpret-parity-tested Pallas kernel when ``use_pallas``).
    O(max_pairs) work — call on the *compacted* emission, never the dense
    stream. Invalid rows score 0.
    """
    ring = state.pk.shape[0]
    i1 = jnp.where(pairs.valid, pairs.idx1, 0) % jnp.int32(ring)
    i2 = jnp.where(pairs.valid, pairs.idx2, 0) % jnp.int32(ring)
    jac = ops.jaccard_popcount(state.pk[i1], state.pk[i2],
                               use_pallas=use_pallas)
    return jnp.where(pairs.valid, jac, jnp.float32(0.0))


def guarded_step(state: IndexState, sigs: jax.Array, buckets: jax.Array,
                 ids: jax.Array, valid: jax.Array | None, cfg: LSHConfig,
                 window: int, saturation: int = 0, dup_tables: int = 0,
                 occ_limit: int = 0, counters: int = 0,
                 packed: jax.Array | None = None, max_pairs: int = 0,
                 verify: int = 0, min_jac: float = 0.0
                 ) -> tuple[IndexState, Pairs, jax.Array]:
    """expire → duplicate guard → insert → saturation-guarded query →
    occurrence limiter → emission compaction + exact-Jaccard verify.

    The one shared insert/query tail of EVERY detection path — the fused
    ``_chunk_core``, the unfused ``stream_step``, and the batch replay
    driver (``core.detect``) — so the guards are bit-identical in all of
    them. Returns (state, pairs, qc) where ``qc`` is the
    ``len(QC_FIELDS)`` counter vector laid out by :data:`QC_FIELDS`: the
    three guard counters (each 0 when the corresponding knob is off —
    the program then matches the unguarded step exactly) followed by the
    telemetry counters (pairs emitted, mask-suppressed fingerprints, raw
    collisions, quarantined collisions), which are computed in the same
    traced program when ``counters`` is set and constant 0 otherwise.
    Counters never feed back into the pair outputs, so detections are
    bit-identical with telemetry on or off (pinned).

    ``occ_limit`` > 0 enables the in-dispatch §6.5 occurrence limiter
    (``occurrence_limit_pairs``): per-fingerprint partner counts carried
    in ``state.occ``, decayed with the sliding window (each incoming id
    reclaims its ring slot — the previous owner is ≥ occ_slots older and
    long expired), capping pair emission per query with no extra
    dispatch. ``window`` > 0 with ``saturation`` > 0 also switches the
    saturation quarantine to the window-relative decaying traffic counter
    (see ``expire``).

    ``max_pairs`` > 0 (static, ISSUE 8) enables the emission epilogue:
    the dense t * N * C pair stream is compacted to a bounded
    ``(max_pairs,)`` buffer (``compact_pairs``; deterministic drop on
    overflow, counted in ``overflow_pairs``), and with ``verify`` > 0
    the compacted candidates are scored with exact Jaccard from the
    bit-packed fingerprint ring (``verify_pairs``; ``packed`` supplies
    this block's (N, pk_words) uint32 rows, written into ``state.pk`` at
    id % pk_slots before the query; ``verify == 2`` routes the scoring
    through the Pallas kernel). The step then returns a
    ``lsh.VerifiedPairs`` — (idx1, idx2, hash matches, jaccard) — and
    ``min_jac`` > 0 drops pairs whose *true* similarity falls below the
    threshold in-dispatch, so downstream thresholds can act on exact
    Jaccard instead of the hash-match proxy. All knobs at 0 leave the
    dense emission and the traced program exactly as before.

    The stages run under ``jax.named_scope`` names ``expire``,
    ``dup_guard``, ``insert``, ``query``, ``limit``, ``compact`` and
    ``verify``, which every device operation carries in its metadata.
    """
    with jax.named_scope("expire"):
        if occ_limit > 0:
            # recycle the incoming ids' partner-count slots (window decay:
            # a slot's previous owner is a full ring behind — outside any
            # window the ring was sized for)
            ring = state.occ.shape[0]
            state = dataclasses.replace(
                state, occ=state.occ.at[ids % ring].set(0))
        if window > 0:
            # newest = one past the last valid id (prefix masks reduce to
            # base + n_valid, the pre-quality behavior; hole-y gap masks
            # still anchor the window to absolute stream time)
            newest = (ids[-1] + 1 if valid is None
                      else jnp.max(jnp.where(valid, ids + 1, ids[0])))
            state = expire(state, newest - jnp.int32(window),
                           half_life=window if saturation > 0 else 0)
    ins_valid, qvalid = valid, None
    qc_dup = jnp.int32(0)
    if dup_tables > 0:
        with jax.named_scope("dup_guard"):
            n = sigs.shape[0]
            v = jnp.ones((n,), bool) if valid is None else valid
            dup = duplicate_flags(state, sigs, ids, cfg, dup_tables,
                                  buckets=buckets, valid=v)
            ins_valid = v & ~dup
            qvalid = ins_valid
            qc_dup = dup.sum(dtype=jnp.int32)
    with jax.named_scope("insert"):
        if verify > 0:
            # stash this block's bit-packed fingerprints in the ring so
            # the verify epilogue can gather both endpoints of any
            # within-window pair (suppressed rows never pair, so their
            # slots stay stale)
            assert max_pairs > 0, "verify requires max_pairs (compaction)"
            ring = state.pk.shape[0]
            wv = (jnp.ones(ids.shape, bool) if ins_valid is None
                  else ins_valid)
            slot = jnp.where(wv, ids % jnp.int32(ring), jnp.int32(ring))
            state = dataclasses.replace(
                state, pk=state.pk.at[slot].set(packed.astype(jnp.uint32),
                                                mode="drop"))
        state = insert(state, sigs, ids, cfg, valid=ins_valid,
                       buckets=buckets)
    with jax.named_scope("query"):
        qc_sat = (saturated_lookup_count(state, buckets, saturation,
                                         valid=ins_valid)
                  if saturation > 0 else jnp.int32(0))
        qc_raw = qc_quar = jnp.int32(0)
        if counters:
            pairs, qcounts = query(state, sigs, ids, cfg, buckets=buckets,
                                   qvalid=qvalid, saturation=saturation,
                                   counts=1)
            qc_raw, qc_quar = qcounts[0], qcounts[1]
        else:
            pairs = query(state, sigs, ids, cfg, buckets=buckets,
                          qvalid=qvalid, saturation=saturation)
    qc_occ = jnp.int32(0)
    if occ_limit > 0:
        with jax.named_scope("limit"):
            state, pairs, qc_occ = occurrence_limit_pairs(
                state, sigs, buckets, ids, qvalid, cfg, pairs, occ_limit)
    qc_overflow = jnp.int32(0)
    if max_pairs > 0:
        with jax.named_scope("compact"):
            pairs, qc_overflow = compact_pairs(pairs, max_pairs)
            jac = jnp.zeros(pairs.valid.shape, jnp.float32)
        if verify > 0:
            with jax.named_scope("verify"):
                jac = verify_pairs(state, pairs, use_pallas=(verify == 2))
                if min_jac > 0.0:
                    keep = pairs.valid & (jac >= jnp.float32(min_jac))
                    pairs = Pairs(idx1=pairs.idx1, idx2=pairs.idx2,
                                  sim=jnp.where(keep, pairs.sim, 0),
                                  valid=keep)
                    jac = jnp.where(keep, jac, jnp.float32(0.0))
        pairs = VerifiedPairs(idx1=pairs.idx1, idx2=pairs.idx2,
                              sim=pairs.sim, jac=jac, valid=pairs.valid)
    qc_pairs = qc_masked = jnp.int32(0)
    if counters:
        qc_pairs = pairs.valid.sum(dtype=jnp.int32)
        if valid is not None:
            qc_masked = (~valid).sum(dtype=jnp.int32)
    return state, pairs, jnp.stack([qc_dup, qc_sat, qc_occ, qc_pairs,
                                    qc_masked, qc_raw, qc_quar,
                                    qc_overflow])


# ---------------------------------------------------------------------------
# station pools: the same IndexState with a leading station axis
# ---------------------------------------------------------------------------


def init_pool(lcfg: LSHConfig, icfg: StreamIndexConfig,
              n_stations: int) -> IndexState:
    """Stacked per-station index: every leaf gains a leading (S,) axis.

    The pool is stepped via ``vmap`` inside the fused chunk step — one
    executable serves S stations (ISSUE 3), instead of S sequential
    engines each paying their own dispatch.
    """
    return stack_states([init_index(lcfg, icfg)] * n_stations)


def stack_states(states: list[IndexState]) -> IndexState:
    """Per-station states → one pool state with a leading station axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def slice_state(pool: IndexState, station: int) -> IndexState:
    """One station's view of a pool state (used by snapshot + serving)."""
    return jax.tree.map(lambda x: x[station], pool)


def index_stats(state: IndexState) -> dict:
    """Occupancy / skew diagnostics (host-side, for monitoring)."""
    occupied = np.asarray(state.ids != INVALID)
    per_bucket = occupied.sum(axis=2)
    return {
        "inserted": int(state.inserted),
        "resident": int(occupied.sum()),
        "occupancy": float(occupied.mean()),
        "full_buckets": int((per_bucket == state.ids.shape[2]).sum()),
        "max_bucket_fill": int(per_bucket.max()),
    }
