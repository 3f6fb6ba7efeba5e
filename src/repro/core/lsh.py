"""MinHash / Min-Max LSH similarity search (paper §6), TPU-native.

The paper's hash-table search is re-expressed as a sort-based group-by
(DESIGN.md §3.1): per hash table, fingerprints sharing a signature form a
run of equal keys after ``lax.sort``; candidate pairs are emitted from a
bounded rank-window (``bucket_cap``) within each run. Mega-buckets — the
exact skew pathology the paper battles in §6.3/§6.5 — are therefore capped
structurally, and the paper's own remedies (more hash functions, the
occurrence filter) make the cap a no-op on healthy data.

Everything is static-shape / mask-based so the whole search jits, shards
(fingerprint axis), and dry-runs on the production meshes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.utils import (fold_hashes, hash_combine, hash_u32, mix32,
                         segment_ids_from_starts, segment_starts)

INVALID = np.int32(np.iinfo(np.int32).max)


@dataclasses.dataclass(frozen=True)
class LSHConfig:
    n_tables: int = 100          # t
    n_funcs: int = 8             # k  (Min-Max evaluates k/2 hash fns)
    n_matches: int = 2           # m  (matches out of t required)
    use_minmax: bool = True      # §6.2 (False = baseline MinHash)
    bucket_cap: int = 8          # rank window per bucket (TPU adaptation)
    min_dt: int = 16             # self-match exclusion (overlapping windows)
    occurrence_frac: float = 0.01  # §6.5 (<=0 disables)
    seed: int = 1234
    use_pallas: bool = False

    @property
    def funcs_per_table(self) -> int:
        return self.n_funcs // 2 if self.use_minmax else self.n_funcs

    @property
    def n_hash_fns(self) -> int:
        return self.n_tables * self.funcs_per_table


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Pairs:
    """Fixed-size masked set of similar-fingerprint pairs.

    idx1 < idx2 where valid; sim = number of hash tables in which the pair
    collided (the paper's similarity proxy and output triplet format §7.2).
    """

    idx1: jax.Array
    idx2: jax.Array
    sim: jax.Array
    valid: jax.Array

    @property
    def dt(self) -> jax.Array:
        return jnp.where(self.valid, self.idx2 - self.idx1, INVALID)

    def count(self) -> jax.Array:
        return self.valid.sum()


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class VerifiedPairs:
    """Compacted pair emission with the exact-similarity verify channel.

    Same masked-pair contract as ``Pairs`` (idx1 < idx2 where valid;
    ``sim`` = number of hash tables matched — the paper's similarity
    proxy), plus ``jac``: exact Jaccard similarity of the two bit-packed
    fingerprints, scored in-dispatch from the index's packed ring
    (ISSUE 8 verify epilogue; all-zero when verification is disabled).
    The arrays are O(max_pairs_per_block), not O(t * N * cap) — this is
    the shape that actually crosses the device→host boundary.
    """

    idx1: jax.Array
    idx2: jax.Array
    sim: jax.Array
    jac: jax.Array
    valid: jax.Array

    @property
    def dt(self) -> jax.Array:
        return jnp.where(self.valid, self.idx2 - self.idx1, INVALID)

    def count(self) -> jax.Array:
        return self.valid.sum()


# ---------------------------------------------------------------------------
# hash mappings + signatures (§6.1–6.2)
# ---------------------------------------------------------------------------


def hash_mappings(d: int, cfg: LSHConfig) -> jax.Array:
    """(d, n_hash_fns) int32 hash values in [0, 2**31).

    The splitmix-style mixer replaces murmurhash (DESIGN.md §3.8): each
    column is an independent random mapping of fingerprint dimensions.
    """
    dims = jnp.arange(d, dtype=jnp.uint32)[:, None]
    fns = jnp.arange(cfg.n_hash_fns, dtype=jnp.uint32)[None, :]
    h = hash_combine(hash_u32(dims, cfg.seed), hash_u32(fns, cfg.seed ^ 0xABCD))
    return (mix32(h) >> 1).astype(jnp.int32)


def signatures(fp: jax.Array, mappings: jax.Array, cfg: LSHConfig,
               valid: jax.Array | None = None) -> jax.Array:
    """Binary fingerprints (N, D) → per-table signatures (N, t) uint32."""
    n = fp.shape[0]
    t, f = cfg.n_tables, cfg.funcs_per_table
    mins, maxs = ops.minmax_hash(fp, mappings, use_pallas=cfg.use_pallas)
    mins = mins.reshape(n, t, f).astype(jnp.uint32)
    if cfg.use_minmax:
        maxs = maxs.reshape(n, t, f).astype(jnp.uint32)
        per_fn = hash_combine(mins, maxs)  # (N, t, f)
    else:
        per_fn = mins
    sig = fold_hashes(per_fn, axis=-1)  # (N, t)
    if valid is not None:
        sig = jnp.where(valid[:, None], sig, _filler_signatures(n, t, cfg))
    return sig


def minhash_signatures_baseline(fp: jax.Array, cfg: LSHConfig) -> jax.Array:
    """Unoptimized MinHash (paper baseline): k hash fns per table."""
    base = dataclasses.replace(cfg, use_minmax=False)
    mp = hash_mappings(fp.shape[1], base)
    return signatures(fp, mp, base)


# ---------------------------------------------------------------------------
# bucket addressing (shared by the streaming index and the fused kernel)
# ---------------------------------------------------------------------------


def bucket_salts(n_tables: int, seed: int) -> jax.Array:
    """(t,) uint32 per-table salts for bucket addressing."""
    return hash_u32(jnp.arange(n_tables, dtype=jnp.uint32), seed ^ 0xB0C4E7)


def bucket_ids(sigs: jax.Array, n_buckets: int, seed: int) -> jax.Array:
    """(N, t) signatures → (N, t) bucket indices, salted per table."""
    salts = bucket_salts(sigs.shape[1], seed)
    h = hash_combine(sigs.astype(jnp.uint32), salts[None, :])
    return (h & jnp.uint32(n_buckets - 1)).astype(jnp.int32)


def _filler_signatures(n: int, t: int, cfg: LSHConfig) -> jax.Array:
    """Unique-ish (N, t) signatures for invalid rows so they never collide."""
    row = hash_u32(jnp.arange(n, dtype=jnp.uint32), cfg.seed ^ 0x5EED)
    tbl = hash_u32(jnp.arange(t, dtype=jnp.uint32), cfg.seed ^ 0x7AB1)
    return hash_combine(row[:, None], tbl[None, :])


def signatures_and_buckets(
    fp: jax.Array, mappings: jax.Array, cfg: LSHConfig, n_buckets: int,
    valid: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fingerprints (N, D) → (signatures (N, t), bucket ids (N, t)).

    The streaming hot path needs both the per-table signature and its
    bucket address; computing them together means the signature fold and
    the bucket hash run once per step instead of once in ``insert`` and
    again in ``query``. With ``use_pallas`` the fold + addressing are fused
    into the Min-Max kernel epilogue (``ops.minmax_sig_buckets``); the jnp
    composition below is the bit-exact oracle.
    """
    n = fp.shape[0]
    t = cfg.n_tables
    if cfg.use_pallas:
        sig, bkt = ops.minmax_sig_buckets(
            fp, mappings, bucket_salts(t, cfg.seed),
            use_minmax=cfg.use_minmax, n_buckets=n_buckets)
    else:
        sig = signatures(fp, mappings, cfg)
        bkt = bucket_ids(sig, n_buckets, cfg.seed)
    if valid is not None:
        filler = _filler_signatures(n, t, cfg)
        sig = jnp.where(valid[:, None], sig, filler)
        bkt = jnp.where(valid[:, None], bkt,
                        bucket_ids(filler, n_buckets, cfg.seed))
    return sig, bkt


# ---------------------------------------------------------------------------
# sort-based bucket group-by → candidate pairs (§6.1 search, TPU-native)
# ---------------------------------------------------------------------------


def _pairs_one_table(keys: jax.Array, cap: int) -> tuple[jax.Array, jax.Array]:
    """(N,) signature keys → (cap*N,) canonical pair endpoints (masked).

    Pairs are emitted between elements at rank distance 1..cap inside runs
    of equal keys. Invalid slots get INVALID endpoints.
    """
    n = keys.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    sk, si = jax.lax.sort((keys, idx), num_keys=1)
    a_all, b_all = [], []
    for w in range(1, cap + 1):
        same = sk[w:] == sk[:-w]
        a = jnp.where(same, si[:-w], INVALID)
        b = jnp.where(same, si[w:], INVALID)
        pad = jnp.full((w,), INVALID, jnp.int32)
        a_all.append(jnp.concatenate([a, pad]))
        b_all.append(jnp.concatenate([b, pad]))
    a = jnp.stack(a_all).reshape(-1)
    b = jnp.stack(b_all).reshape(-1)
    lo = jnp.minimum(a, b)
    hi = jnp.maximum(a, b)
    return lo, hi


def count_pair_multiplicity(lo: jax.Array, hi: jax.Array,
                            n_matches: int) -> Pairs:
    """Sort all (lo, hi) pairs; count duplicates (= #tables matched)."""
    p = lo.shape[0]
    lo_s, hi_s = jax.lax.sort((lo, hi), num_keys=2)
    starts = segment_starts(lo_s) | segment_starts(hi_s)
    seg = segment_ids_from_starts(starts)
    ones = (lo_s != INVALID).astype(jnp.int32)
    counts = jax.ops.segment_sum(ones, seg, num_segments=p)
    sim = counts[seg]
    valid = starts & (lo_s != INVALID) & (sim >= n_matches)
    return Pairs(idx1=lo_s, idx2=hi_s, sim=jnp.where(valid, sim, 0),
                 valid=valid)


def finalize_pairs(lo: jax.Array, hi: jax.Array, cfg: LSHConfig) -> Pairs:
    """Canonical endpoint streams → thresholded Pairs (shared batch/stream).

    Applies the self-match exclusion (``min_dt``) and the m-of-t collision
    threshold (``n_matches``). ``lo``/``hi`` are flat per-table emission
    streams with INVALID in masked slots; a pair's similarity is its
    multiplicity across the streams (= #tables in which it collided).
    Both the offline sort-based search and the streaming index query end
    in exactly this reduction.
    """
    if cfg.min_dt > 0:  # self-match exclusion
        ok = (hi - lo) >= cfg.min_dt
        lo = jnp.where(ok, lo, INVALID)
        hi = jnp.where(ok, hi, INVALID)
    return count_pair_multiplicity(lo, hi, cfg.n_matches)


@functools.partial(jax.jit, static_argnames=("cfg",))
def candidate_pairs(sigs: jax.Array, cfg: LSHConfig) -> Pairs:
    """(N, t) signatures → Pairs of size t * bucket_cap * N (masked)."""
    n, t = sigs.shape
    lo, hi = jax.vmap(lambda k: _pairs_one_table(k, cfg.bucket_cap),
                      in_axes=1)(sigs)  # (t, cap*N) each
    return finalize_pairs(lo.reshape(-1), hi.reshape(-1), cfg)


# ---------------------------------------------------------------------------
# occurrence filter (§6.5)
# ---------------------------------------------------------------------------


def occurrence_filter(pairs: Pairs, n_fp: int, frac: float,
                      limit: int | None = None) -> tuple[Pairs, jax.Array]:
    """Drop fingerprints matching more than ``frac`` of the partition.

    Also drops their match partners (the paper excludes "this fingerprint
    as well as its neighbors"). Returns (filtered pairs, excluded mask).

    ``n_fp`` sizes the id space (segment count); ``limit`` overrides the
    occurrence cap when the partition whose fraction is meant differs from
    the id space — the rolling streaming filter counts occurrences over a
    window of ids whose partners may reach back a further lookback span.
    """
    v = pairs.valid
    i1 = jnp.where(v, pairs.idx1, 0)
    i2 = jnp.where(v, pairs.idx2, 0)
    w = v.astype(jnp.int32)
    cnt = (jax.ops.segment_sum(w, i1, num_segments=n_fp)
           + jax.ops.segment_sum(w, i2, num_segments=n_fp))
    limit = jnp.int32(max(1, int(frac * n_fp)) if limit is None
                      else max(1, int(limit)))
    excluded = cnt > limit
    # neighbors of excluded fingerprints
    nb1 = jax.ops.segment_max(jnp.where(v, excluded[i2].astype(jnp.int32), 0),
                              i1, num_segments=n_fp)
    nb2 = jax.ops.segment_max(jnp.where(v, excluded[i1].astype(jnp.int32), 0),
                              i2, num_segments=n_fp)
    excluded_full = excluded | (nb1 > 0) | (nb2 > 0)
    new_valid = v & ~excluded_full[i1] & ~excluded_full[i2]
    out = Pairs(idx1=pairs.idx1, idx2=pairs.idx2,
                sim=jnp.where(new_valid, pairs.sim, 0), valid=new_valid)
    return out, excluded_full


def occurrence_keep(idx1: np.ndarray, idx2: np.ndarray, n_fp: int,
                    frac: float, limit: int | None = None) -> np.ndarray:
    """``occurrence_filter`` on the host, in numpy: the mask of the pairs
    (ids in [0, n_fp)) that survive it, bit for bit the jitted filter's
    ``valid``. The rolling filter's window close runs this, so a close
    of any size compiles nothing."""
    cnt = (np.bincount(idx1, minlength=n_fp)
           + np.bincount(idx2, minlength=n_fp))
    limit = max(1, int(frac * n_fp)) if limit is None else max(1, int(limit))
    excluded = cnt > limit
    full = excluded.copy()
    full[idx1[excluded[idx2]]] = True
    full[idx2[excluded[idx1]]] = True
    return ~full[idx1] & ~full[idx2]


# ---------------------------------------------------------------------------
# whole search (+ partitioned variant, §6.4)
# ---------------------------------------------------------------------------


def search(fp: jax.Array, cfg: LSHConfig,
           valid: jax.Array | None = None) -> tuple[Pairs, dict]:
    """Fingerprints (N, D) → similar pairs + search statistics."""
    n = fp.shape[0]
    mp = hash_mappings(fp.shape[1], cfg)
    sigs = signatures(fp, mp, cfg, valid=valid)
    pairs = candidate_pairs(sigs, cfg)
    stats = {"pre_filter_pairs": pairs.count()}
    if cfg.occurrence_frac > 0:
        pairs, excluded = occurrence_filter(pairs, n, cfg.occurrence_frac)
        stats["excluded_fingerprints"] = excluded.sum()
    stats["pairs"] = pairs.count()
    stats.update(bucket_stats(sigs))
    return pairs, stats


def partitioned_search(fp: jax.Array, cfg: LSHConfig,
                       n_partitions: int) -> tuple[list[Pairs], dict]:
    """§6.4: memory-bounded search over partition pair-blocks.

    Signatures are computed once; candidate generation sorts only the keys
    of one partition-block (p, q) at a time, so the working set shrinks by
    ~n_partitions while results stay exactly the union over blocks (each
    cross pair lives in exactly one block).
    """
    n = fp.shape[0]
    assert n % n_partitions == 0, (n, n_partitions)
    psize = n // n_partitions
    mp = hash_mappings(fp.shape[1], cfg)
    sigs = signatures(fp, mp, cfg)

    @functools.partial(jax.jit, static_argnames=("intra",))
    def block(sig_a, base_a, sig_b, base_b, intra: bool):
        if intra:
            sig = sig_a
            gids = base_a + jnp.arange(psize, dtype=jnp.int32)
        else:
            sig = jnp.concatenate([sig_a, sig_b])
            gids = jnp.concatenate([
                base_a + jnp.arange(psize, dtype=jnp.int32),
                base_b + jnp.arange(psize, dtype=jnp.int32)])
        pr = candidate_pairs(sig, cfg)
        # local → global ids; for cross blocks keep only cross pairs
        g1 = jnp.where(pr.valid, gids[jnp.where(pr.valid, pr.idx1, 0)], INVALID)
        g2 = jnp.where(pr.valid, gids[jnp.where(pr.valid, pr.idx2, 0)], INVALID)
        val = pr.valid
        if not intra:
            cross = ((pr.idx1 < psize) & (pr.idx2 >= psize))
            val = val & cross
        lo = jnp.minimum(g1, g2)
        hi = jnp.maximum(g1, g2)
        if cfg.min_dt > 0:
            val = val & ((hi - lo) >= cfg.min_dt)
        return Pairs(idx1=jnp.where(val, lo, INVALID),
                     idx2=jnp.where(val, hi, INVALID),
                     sim=jnp.where(val, pr.sim, 0), valid=val)

    out: list[Pairs] = []
    for p in range(n_partitions):
        sa = sigs[p * psize:(p + 1) * psize]
        for q in range(p, n_partitions):
            sb = sigs[q * psize:(q + 1) * psize]
            out.append(block(sa, jnp.int32(p * psize), sb,
                             jnp.int32(q * psize), p == q))
    stats = {
        "blocks": len(out),
        "block_sort_keys": (2 * psize) * cfg.n_tables,
        "working_set_bytes": 2 * psize * cfg.n_tables
        * (4 + 4) * cfg.bucket_cap,
    }
    return out, stats


# ---------------------------------------------------------------------------
# diagnostics (§6.3) + exact verification
# ---------------------------------------------------------------------------


def bucket_stats(sigs: jax.Array) -> dict:
    """Skew diagnostics: selectivity, lookups/query, largest-bucket mass."""
    n, t = sigs.shape

    def per_table(keys):
        sk = jax.lax.sort(keys)
        starts = segment_starts(sk)
        seg = segment_ids_from_starts(starts)
        sizes = jax.ops.segment_sum(jnp.ones_like(sk, jnp.int32), seg,
                                    num_segments=n)
        lookups = (sizes * (sizes - 1)).sum()  # sum_b s(s-1)
        return lookups, sizes.max()

    lookups, max_bucket = jax.vmap(per_table, in_axes=1)(sigs)
    avg_lookups_per_query = lookups.sum() / (n * t)
    return {
        "selectivity": avg_lookups_per_query / n,
        "avg_lookups_per_query": avg_lookups_per_query,
        "max_bucket": max_bucket.max(),
    }


def verify_jaccard(packed: jax.Array, pairs: Pairs,
                   use_pallas: bool = False) -> jax.Array:
    """Exact Jaccard for candidate pairs from packed fingerprints."""
    i1 = jnp.where(pairs.valid, pairs.idx1, 0)
    i2 = jnp.where(pairs.valid, pairs.idx2, 0)
    sim = ops.jaccard_popcount(packed[i1], packed[i2], use_pallas=use_pallas)
    return jnp.where(pairs.valid, sim, 0.0)


def brute_force_pairs(fp: jax.Array, threshold: float,
                      min_dt: int = 0) -> np.ndarray:
    """O(N²) exact Jaccard join (test/benchmark oracle). Returns (P, 3)."""
    fpb = np.asarray(fp, dtype=bool)
    inter = (fpb.astype(np.int32) @ fpb.T.astype(np.int32))
    sizes = fpb.sum(1)
    union = sizes[:, None] + sizes[None, :] - inter
    jac = np.where(union > 0, inter / np.maximum(union, 1), 0.0)
    n = fpb.shape[0]
    iu = np.triu_indices(n, k=max(1, min_dt))
    mask = jac[iu] >= threshold
    return np.stack([iu[0][mask], iu[1][mask], jac[iu][mask]], axis=1)
