"""Spatiotemporal alignment (paper §7): triplets → earthquake detections.

Channel level: sort-merge-reduce of per-channel (dt, idx1, sim) triplets.
Station level: gap-tolerant clustering along similarity-matrix diagonals,
with a single merge pass across adjacent diagonals.
Network level: association across stations using the physical invariance of
inter-event time (Figure 9): groups sharing dt (±tol) and onset (±tol) at
≥ ``min_stations`` distinct stations become detections.

On-device the paper's out-of-core sort (§7.2) becomes ``lax.sort`` + segment
reductions (the pod's aggregate HBM replaces single-node disk; DESIGN.md
§3.6); ``align_streamed`` keeps a host-side external-merge path for outputs
larger than memory.
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
import os
import tempfile
from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lsh import INVALID, Pairs
from repro.utils import segment_ids_from_starts, segment_starts


@dataclasses.dataclass(frozen=True)
class AlignConfig:
    channel_threshold: int = 4     # combined-sim threshold after merge
    gap: int = 10                  # max idx1 gap within a diagonal cluster
    dt_merge_tol: int = 2          # adjacent-diagonal merge distance
    min_cluster_size: int = 2      # prune small clusters
    min_cluster_sim: int = 6
    dt_tol: int = 2                # network: inter-event-time tolerance
    onset_tol: int = 30            # network: arrival-window tolerance
    min_stations: int = 2
    # network groups start on *consecutive* deltas, so a chain of onsets
    # each within onset_tol can link events spanning many tolerances into
    # one group. The cap bounds a group's onset span (> 0); chains beyond
    # it are dropped as physically implausible — no single origin produces
    # arrivals that far apart (the locate tier's moveout-consistency
    # check is the model-based version of the same bound). 0 = unbounded.
    max_group_extent: int = 0


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Events:
    """Per-station candidate events (masked). onset/dt in fingerprint lags."""

    dt: jax.Array
    onset: jax.Array
    extent: jax.Array     # idx_max - idx_min of the cluster
    size: jax.Array       # similar-pair count in the cluster
    score: jax.Array      # summed similarity
    valid: jax.Array

    def count(self) -> jax.Array:
        return self.valid.sum()


# ---------------------------------------------------------------------------
# channel level
# ---------------------------------------------------------------------------


def _sort_triplets(dt, idx1, sim, valid):
    k1 = jnp.where(valid, dt, INVALID)
    k2 = jnp.where(valid, idx1, INVALID)
    return jax.lax.sort((k1, k2, sim, valid.astype(jnp.int32)), num_keys=2)


@functools.partial(jax.jit, static_argnames=("threshold",))
def merge_channels(triplets: Sequence[tuple], threshold: int) -> Pairs:
    """Sum similarity of identical (dt, idx1) across channels; threshold.

    ``triplets``: sequence of (dt, idx1, sim, valid) arrays, one per
    component of a station (a one-component station passes one).
    Implements the paper's sort → merge → reduce with the combined-matrix
    threshold (§7.1 channel level). ``merge_channels_host`` is its numpy
    twin for the rolling filter's window close.
    """
    dt = jnp.concatenate([t[0] for t in triplets])
    idx1 = jnp.concatenate([t[1] for t in triplets])
    sim = jnp.concatenate([t[2] for t in triplets])
    valid = jnp.concatenate([t[3].astype(bool) for t in triplets])
    dt_s, idx_s, sim_s, val_s = _sort_triplets(dt, idx1, sim, valid)
    p = dt_s.shape[0]
    starts = segment_starts(dt_s) | segment_starts(idx_s)
    seg = segment_ids_from_starts(starts)
    tot = jax.ops.segment_sum(jnp.where(val_s > 0, sim_s, 0), seg,
                              num_segments=p)
    keep = starts & (val_s > 0) & (tot[seg] >= threshold)
    idx2 = jnp.where(keep, idx_s + dt_s, INVALID)
    return Pairs(idx1=jnp.where(keep, idx_s, INVALID), idx2=idx2,
                 sim=jnp.where(keep, tot[seg], 0), valid=keep)


def _runs(*keys: np.ndarray) -> np.ndarray:
    """Start positions of the runs of equal key tuples (keys sorted)."""
    new = np.zeros(keys[0].size, bool)
    new[:1] = True
    for k in keys:
        new[1:] |= k[1:] != k[:-1]
    return np.flatnonzero(new)


def merge_channels_host(triplets: Sequence[np.ndarray],
                        threshold: int) -> np.ndarray:
    """``merge_channels`` on the host, in numpy, for the rolling filter's
    window close: per channel an (m, 3) array of (idx1, idx2, sim), out
    the (k, 3) merged triplets (idx1, idx2, summed sim) that reach
    ``threshold``, sorted by (dt, idx1) — the jitted merge's valid pairs
    in its order."""
    tri = np.concatenate([np.asarray(t, np.int64).reshape(-1, 3)
                          for t in triplets])
    if not tri.shape[0]:
        return tri
    dt = tri[:, 1] - tri[:, 0]
    order = np.lexsort((tri[:, 0], dt))
    dt, idx1, sim = dt[order], tri[order, 0], tri[order, 2]
    starts = _runs(dt, idx1)
    tot = np.add.reduceat(sim, starts)
    keep = tot >= threshold
    i1 = idx1[starts][keep]
    return np.stack([i1, i1 + dt[starts][keep], tot[keep]], axis=1)


# ---------------------------------------------------------------------------
# station level
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg",))
def cluster_station(pairs: Pairs, cfg: AlignConfig) -> Events:
    """Cluster triplets along diagonals into candidate events (§7.1/7.2).

    Stage 1: sort by (dt, idx1); a new cluster starts on a dt change or an
    idx1 gap > ``gap`` (pure local boundary ⇒ no sequential scan; the
    paper's partition-point search degenerates to these boundaries).
    Stage 2: one merge pass over clusters sorted by (idx_min, dt), joining
    clusters within ``dt_merge_tol`` diagonals whose idx ranges are within
    ``gap`` (single-pass approximation of the paper's iterative merge).
    """
    dt, idx1 = pairs.dt, pairs.idx1
    sim, valid = pairs.sim, pairs.valid
    dt_s, idx_s, sim_s, val_s = _sort_triplets(dt, idx1, sim, valid)
    p = dt_s.shape[0]

    # --- stage 1: per-diagonal gap clustering
    prev_dt = jnp.concatenate([jnp.array([INVALID]), dt_s[:-1]])
    prev_ix = jnp.concatenate([jnp.array([INVALID]), idx_s[:-1]])
    new = ((dt_s != prev_dt)
           | ((idx_s - prev_ix) > cfg.gap)
           | (val_s == 0))
    cid = segment_ids_from_starts(new)
    w = (val_s > 0).astype(jnp.int32)
    c_count = jax.ops.segment_sum(w, cid, num_segments=p)
    c_score = jax.ops.segment_sum(jnp.where(val_s > 0, sim_s, 0), cid,
                                  num_segments=p)
    c_dt = jax.ops.segment_min(jnp.where(val_s > 0, dt_s, INVALID), cid,
                               num_segments=p)
    c_imin = jax.ops.segment_min(jnp.where(val_s > 0, idx_s, INVALID), cid,
                                 num_segments=p)
    c_imax = jax.ops.segment_max(jnp.where(val_s > 0, idx_s, -1), cid,
                                 num_segments=p)
    c_valid = c_count > 0

    # --- stage 2: adjacent-diagonal merge (sort clusters by idx_min, dt)
    k1 = jnp.where(c_valid, c_imin, INVALID)
    k2 = jnp.where(c_valid, c_dt, INVALID)
    s_imin, s_dt, s_imax, s_count, s_score, s_val = jax.lax.sort(
        (k1, k2, c_imax, c_count, c_score, c_valid.astype(jnp.int32)),
        num_keys=2)
    pdt = jnp.concatenate([jnp.array([INVALID]), s_dt[:-1]])
    pimax = jnp.concatenate([jnp.array([-INVALID]), s_imax[:-1]])
    sep = ((jnp.abs(s_dt - pdt) > cfg.dt_merge_tol)
           | (s_imin > pimax + cfg.gap)
           | (s_val == 0))
    gid = segment_ids_from_starts(sep)
    g_count = jax.ops.segment_sum(jnp.where(s_val > 0, s_count, 0), gid,
                                  num_segments=p)
    g_score = jax.ops.segment_sum(jnp.where(s_val > 0, s_score, 0), gid,
                                  num_segments=p)
    g_dt = jax.ops.segment_min(jnp.where(s_val > 0, s_dt, INVALID), gid,
                               num_segments=p)
    g_imin = jax.ops.segment_min(jnp.where(s_val > 0, s_imin, INVALID), gid,
                                 num_segments=p)
    g_imax = jax.ops.segment_max(jnp.where(s_val > 0, s_imax, -1), gid,
                                 num_segments=p)
    rep = sep & (s_val > 0)
    keep = (rep & (g_count[gid] >= cfg.min_cluster_size)
            & (g_score[gid] >= cfg.min_cluster_sim))
    return Events(dt=jnp.where(keep, g_dt[gid], INVALID),
                  onset=jnp.where(keep, g_imin[gid], INVALID),
                  extent=jnp.where(keep, g_imax[gid] - g_imin[gid], 0),
                  size=jnp.where(keep, g_count[gid], 0),
                  score=jnp.where(keep, g_score[gid], 0),
                  valid=keep)


def cluster_station_host(tri: np.ndarray, cfg: AlignConfig) -> np.ndarray:
    """``cluster_station`` on the host, in numpy, over (m, 3) triplets
    (idx1, idx2, sim) with distinct (dt, idx1), as
    ``merge_channels_host`` gives them: the (k, 5) event rows (dt, onset,
    extent, size, score) of the jitted clustering's valid events, in its
    order (the stage-2 sort by onset, then dt)."""
    tri = np.asarray(tri, np.int64).reshape(-1, 3)
    if not tri.shape[0]:
        return np.zeros((0, 5), np.int64)
    dt = tri[:, 1] - tri[:, 0]
    order = np.lexsort((tri[:, 0], dt))
    dt, idx1, sim = dt[order], tri[order, 0], tri[order, 2]
    # stage 1: a cluster starts on a dt change or an idx1 gap > gap
    new = np.ones(dt.size, bool)
    new[1:] = (dt[1:] != dt[:-1]) | (idx1[1:] - idx1[:-1] > cfg.gap)
    c = np.flatnonzero(new)
    c_count = np.diff(np.r_[c, dt.size])
    c_score = np.add.reduceat(sim, c)
    c_dt, c_imin = dt[c], idx1[c]
    c_imax = np.maximum.reduceat(idx1, c)
    # stage 2: one merge pass over clusters sorted by (idx_min, dt), each
    # against the cluster before it
    o = np.lexsort((c_dt, c_imin))
    s_dt, s_imin, s_imax = c_dt[o], c_imin[o], c_imax[o]
    s_count, s_score = c_count[o], c_score[o]
    sep = np.ones(o.size, bool)
    sep[1:] = ((np.abs(s_dt[1:] - s_dt[:-1]) > cfg.dt_merge_tol)
               | (s_imin[1:] > s_imax[:-1] + cfg.gap))
    g = np.flatnonzero(sep)
    g_count = np.add.reduceat(s_count, g)
    g_score = np.add.reduceat(s_score, g)
    g_imin = np.minimum.reduceat(s_imin, g)
    rows = np.stack([np.minimum.reduceat(s_dt, g), g_imin,
                     np.maximum.reduceat(s_imax, g) - g_imin, g_count,
                     g_score], axis=1)
    keep = (g_count >= cfg.min_cluster_size) & (g_score >= cfg.min_cluster_sim)
    return rows[keep]


# ---------------------------------------------------------------------------
# network level
# ---------------------------------------------------------------------------


def _segment_or(flags: jax.Array, words: jax.Array) -> jax.Array:
    """Running bitwise-OR of ``words`` within segments started by ``flags``.

    Classic segmented-scan monoid over (flag, value) pairs: a right
    operand that starts a segment resets the carry, so the OR never leaks
    across segment boundaries. Returns the per-row prefix OR; the full
    segment OR sits at each segment's last row.
    """

    def op(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb[:, None], vb, va | vb)

    _, run = jax.lax.associative_scan(op, (flags, words))
    return run


@functools.partial(jax.jit, static_argnames=("cfg", "n_stations",
                                             "with_onsets"))
def associate_network(events: Sequence[Events], cfg: AlignConfig,
                      n_stations: int, with_onsets: bool = False) -> dict:
    """Group per-station events by (dt, onset); require ≥ min_stations.

    Exploits the inter-event-time invariance (Figure 9): the same pair of
    reoccurring earthquakes shows the same dt at every station, with close
    onsets. Station multiplicity uses packed int32 bitmask words (32
    stations per word, ``ceil(S/32)`` words per row) segment-OR'd and
    popcounted — O(p·⌈S/32⌉) memory with no station cap, so the sharded
    100s-of-stations pool feeds through the same path.

    ``max_group_extent`` > 0 drops groups whose onset span exceeds it
    (tolerance-chaining bound; see AlignConfig). ``with_onsets`` adds the
    dense per-group (p, S) station onset / score matrices the locate tier
    stacks over — opt-in because they are the one O(p·S) output here.
    """
    if n_stations <= 0:
        raise ValueError(f"n_stations must be positive, got {n_stations}")
    if len(events) != n_stations:
        raise ValueError(f"got {len(events)} per-station Events for "
                         f"n_stations={n_stations}")
    dt = jnp.concatenate([e.dt for e in events])
    onset = jnp.concatenate([e.onset for e in events])
    score = jnp.concatenate([e.score for e in events])
    valid = jnp.concatenate([e.valid for e in events])
    sid = jnp.concatenate([
        jnp.full(e.dt.shape, i, jnp.int32) for i, e in enumerate(events)])
    p = dt.shape[0]
    k1 = jnp.where(valid, dt, INVALID)
    k2 = jnp.where(valid, onset, INVALID)
    dt_s, on_s, sc_s, sid_s, val_s = jax.lax.sort(
        (k1, k2, score, sid, valid.astype(jnp.int32)), num_keys=2)
    pdt = jnp.concatenate([jnp.array([INVALID]), dt_s[:-1]])
    pon = jnp.concatenate([jnp.array([INVALID]), on_s[:-1]])
    new = ((jnp.abs(dt_s - pdt) > cfg.dt_tol)
           | (jnp.abs(on_s - pon) > cfg.onset_tol)
           | (val_s == 0))
    gid = segment_ids_from_starts(new)
    # packed station bitmask: word w of row r holds bit (sid mod 32) iff
    # sid div 32 == w. Rows are gid-contiguous after the sort, so a
    # segmented prefix-OR + the segment's last row gives the group's
    # station set; popcount sums the multiplicity.
    n_words = -(-n_stations // 32)
    bit = jnp.where(val_s > 0,
                    jnp.left_shift(jnp.uint32(1),
                                   (sid_s % 32).astype(jnp.uint32)),
                    jnp.uint32(0))
    words = jnp.where((sid_s // 32)[:, None]
                      == jnp.arange(n_words, dtype=sid_s.dtype)[None, :],
                      bit[:, None], jnp.uint32(0))
    run_or = _segment_or(new, words)
    last = jnp.clip(jax.ops.segment_max(jnp.arange(p), gid, num_segments=p),
                    0, p - 1)
    n_st = jax.lax.population_count(run_or[last]).sum(
        axis=1).astype(jnp.int32)
    g_score = jax.ops.segment_sum(jnp.where(val_s > 0, sc_s, 0), gid,
                                  num_segments=p)
    g_dt = jax.ops.segment_min(jnp.where(val_s > 0, dt_s, INVALID), gid,
                               num_segments=p)
    g_onset = jax.ops.segment_min(jnp.where(val_s > 0, on_s, INVALID), gid,
                                  num_segments=p)
    g_on_max = jax.ops.segment_max(jnp.where(val_s > 0, on_s, -1), gid,
                                   num_segments=p)
    span = jnp.maximum(g_on_max - g_onset, 0)
    rep = new & (val_s > 0)
    keep = rep & (n_st[gid] >= cfg.min_stations)
    if cfg.max_group_extent > 0:
        keep &= span[gid] <= cfg.max_group_extent
    out = {
        "dt": jnp.where(keep, g_dt[gid], INVALID),
        "onset": jnp.where(keep, g_onset[gid], INVALID),
        "onset_span": jnp.where(keep, span[gid], 0),
        "n_stations": jnp.where(keep, n_st[gid], 0),
        "score": jnp.where(keep, g_score[gid], 0),
        "valid": keep,
    }
    if with_onsets:
        on_station = (sid_s[:, None]
                      == jnp.arange(n_stations, dtype=sid_s.dtype)[None, :])
        live = on_station & (val_s > 0)[:, None]
        onset_mat = jax.ops.segment_min(
            jnp.where(live, on_s[:, None], INVALID), gid, num_segments=p)
        score_mat = jax.ops.segment_sum(
            jnp.where(live, sc_s[:, None], 0), gid, num_segments=p)
        out["station_onset"] = jnp.where(keep[:, None], onset_mat[gid],
                                         INVALID)
        out["station_score"] = jnp.where(keep[:, None], score_mat[gid], 0)
    return out


def associate_network_host(rows: Sequence[np.ndarray], cfg: AlignConfig,
                           with_onsets: bool = False) -> dict:
    """``associate_network`` on the host, in numpy, over each station's
    (k, 5) event rows (dt, onset, extent, size, score): the valid groups
    of the jitted association, in its order, as compact arrays ("dt",
    "onset", "onset_span", "n_stations", "score", and with
    ``with_onsets`` the (groups, S) "station_onset" / "station_score").
    The detector's per-push poll runs this, so an association of any
    size compiles nothing."""
    n_stations = len(rows)
    rows = [np.asarray(r, np.int64).reshape(-1, 5) for r in rows]
    sid = np.concatenate([np.full(r.shape[0], i, np.int64)
                          for i, r in enumerate(rows)])
    allr = np.concatenate(rows)
    order = np.lexsort((allr[:, 1], allr[:, 0]))
    dt, on, sc, sid = allr[order, 0], allr[order, 1], allr[order, 4], \
        sid[order]
    new = np.ones(dt.size, bool)
    new[1:] = ((np.abs(dt[1:] - dt[:-1]) > cfg.dt_tol)
               | (np.abs(on[1:] - on[:-1]) > cfg.onset_tol))
    g = np.flatnonzero(new)
    gid = np.cumsum(new) - 1
    n_g = g.size
    member = np.zeros((n_g, n_stations), bool)
    member[gid, sid] = True
    n_st = member.sum(axis=1)
    if n_g:
        g_dt = np.minimum.reduceat(dt, g)
        g_on = np.minimum.reduceat(on, g)
        span = np.maximum(np.maximum.reduceat(on, g) - g_on, 0)
        g_sc = np.add.reduceat(sc, g)
    else:
        g_dt = g_on = span = g_sc = np.zeros(0, np.int64)
    keep = n_st >= cfg.min_stations
    if cfg.max_group_extent > 0:
        keep &= span <= cfg.max_group_extent
    out = {"dt": g_dt[keep], "onset": g_on[keep], "onset_span": span[keep],
           "n_stations": n_st[keep], "score": g_sc[keep]}
    if with_onsets:
        onset_mat = np.full((n_g, n_stations), int(INVALID), np.int64)
        np.minimum.at(onset_mat, (gid, sid), on)
        score_mat = np.zeros((n_g, n_stations), np.int64)
        np.add.at(score_mat, (gid, sid), sc)
        out["station_onset"] = onset_mat[keep]
        out["station_score"] = score_mat[keep]
    return out


# ---------------------------------------------------------------------------
# out-of-core channel merge (paper §7.2, host-side)
# ---------------------------------------------------------------------------


def align_streamed(channel_chunks: Sequence[Iterable[np.ndarray]],
                   threshold: int, tmpdir: str | None = None) -> np.ndarray:
    """External sort-merge-reduce of triplet chunks larger than memory.

    ``channel_chunks``: per channel, an iterable of (n, 3) int arrays with
    columns (dt, idx1, sim). Each chunk is sorted and spilled to disk; a
    heap merge streams them back, reducing consecutive equal (dt, idx1)
    rows and applying the combined threshold. Returns (m, 3) array.
    """
    tmp = tmpdir or tempfile.mkdtemp(prefix="fast_align_")
    spill_files = []
    for ci, chunks in enumerate(channel_chunks):
        for gi, arr in enumerate(chunks):
            arr = np.asarray(arr, np.int64)
            order = np.lexsort((arr[:, 1], arr[:, 0]))
            path = os.path.join(tmp, f"c{ci}_g{gi}.npy")
            np.save(path, arr[order])
            spill_files.append(path)

    def stream(path):
        arr = np.load(path, mmap_mode="r")
        for row in arr:
            yield (int(row[0]), int(row[1]), int(row[2]))

    out = []
    cur_key, cur_sim = None, 0
    for dt, idx1, sim in heapq.merge(*[stream(p) for p in spill_files]):
        if (dt, idx1) == cur_key:
            cur_sim += sim
        else:
            if cur_key is not None and cur_sim >= threshold:
                out.append((cur_key[0], cur_key[1], cur_sim))
            cur_key, cur_sim = (dt, idx1), sim
    if cur_key is not None and cur_sim >= threshold:
        out.append((cur_key[0], cur_key[1], cur_sim))
    return np.asarray(out, np.int64).reshape(-1, 3)
