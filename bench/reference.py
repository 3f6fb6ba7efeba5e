"""Plain reference of the detector's semantics, independent of the program.

Nothing here imports the program. From the configuration's numbers alone
it computes, for one station's waveform:

* the binary fingerprints (paper §5): power spectrogram by DFT matmul
  over Hann-windowed frames, cut to the band, averaged into ``img_freq``
  bins, sliced into ``img_time``-frame images every ``img_hop`` frames,
  2-D Haar transform, median/MAD normalisation with the given statistics,
  the ``top_k`` largest |z| kept as two sign bits per coefficient. Every
  matmul is float32 at HIGHEST precision;
* the Min-Max LSH signature per table and its bucket address (paper §6.2,
  the splitmix/murmur-finaliser hash family keyed by the LSH seed);
* the pairs the resident index emits block by block: a bucket keeps its
  ``bucket_cap`` newest entries, a query pairs with stored entries of
  equal signature at id distance ≥ ``min_dt``, buckets whose insert
  traffic passed ``saturation_limit`` emit nothing, a pair needs
  ``n_matches`` tables, pairs touching a fingerprint whose running count
  of raw collisions passed ``occ_limit`` are dropped, and each block keeps
  at most ``max_pairs_per_block`` pairs, the smallest (idx1, idx2) first;
* exact Jaccard similarity of two packed fingerprints.

The fingerprint and hash stages run on the default device in blocks of
rows; the pair model runs in numpy.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
INVALID = np.iinfo(np.int32).max

_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)
_GOLDEN = np.uint32(0x9E3779B9)


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------


def band_bins(fp: dict) -> tuple[int, int]:
    n_rfft = fp["stft_len"] // 2 + 1
    lo = int(math.ceil(fp["band_lo_hz"] * fp["stft_len"] / fp["fs"]))
    hi = int(math.floor(fp["band_hi_hz"] * fp["stft_len"] / fp["fs"])) + 1
    lo = max(0, min(lo, n_rfft - 1))
    return lo, max(lo + 1, min(hi, n_rfft))


def window_samples(fp: dict) -> int:
    return (fp["img_time"] - 1) * fp["stft_hop"] + fp["stft_len"]


def lag_samples(fp: dict) -> int:
    return fp["img_hop"] * fp["stft_hop"]


def n_fingerprints(fp: dict, n_samples: int) -> int:
    frames = max(0, (n_samples - fp["stft_len"]) // fp["stft_hop"] + 1)
    return max(0, (frames - fp["img_time"]) // fp["img_hop"] + 1)


def span_samples(fp: dict, n_fp: int) -> int:
    """Samples spanned by ``n_fp`` consecutive fingerprints."""
    return (n_fp - 1) * lag_samples(fp) + window_samples(fp)


def pool_matrix(n_in: int, n_out: int) -> np.ndarray:
    """Average-pooling matrix (n_in, n_out) over near-equal bin spans."""
    edges = np.linspace(0, n_in, n_out + 1)
    m = np.zeros((n_in, n_out), np.float32)
    for j in range(n_out):
        lo, hi = edges[j], edges[j + 1]
        for i in range(int(np.floor(lo)), int(np.ceil(hi))):
            w = min(hi, i + 1) - max(lo, i)
            if w > 0:
                m[i, j] = w
    m /= m.sum(axis=0, keepdims=True)
    return m


def haar_matrix(n: int) -> np.ndarray:
    """Orthonormal multilevel 1-D Haar transform (n × n), rows ordered
    [approximation, coarsest detail, ..., finest detail]."""
    h = np.array([[1.0]])
    while h.shape[0] < n:
        m = h.shape[0]
        top = np.kron(h, np.array([[1.0, 1.0]]) / math.sqrt(2.0))
        bot = np.kron(np.eye(m), np.array([[1.0, -1.0]]) / math.sqrt(2.0))
        h = np.concatenate([top, bot], axis=0)
    return h.astype(np.float32)


def _constants(fp: dict) -> dict:
    lo, hi = band_bins(fp)
    n = fp["stft_len"]
    t = np.arange(n)[:, None]
    k = np.arange(lo, hi)[None, :]
    ang = -2.0 * np.pi * t * k / n
    return {"win": np.hanning(n).astype(np.float32),
            "dr": np.cos(ang).astype(np.float32),
            "di": np.sin(ang).astype(np.float32),
            "pool": pool_matrix(hi - lo, fp["img_freq"]),
            "hf": haar_matrix(fp["img_freq"]),
            "ht": haar_matrix(fp["img_time"])}


def coefficients_np(fp: dict, x: np.ndarray) -> np.ndarray:
    """Haar coefficients (N, img_freq * img_time) of a waveform, in numpy
    float32 on the host (used for the frozen statistics)."""
    c = _constants(fp)
    n_fr = (x.shape[-1] - fp["stft_len"]) // fp["stft_hop"] + 1
    idx = (np.arange(n_fr)[:, None] * fp["stft_hop"]
           + np.arange(fp["stft_len"])[None, :])
    xw = x[idx] * c["win"]
    spec = (xw @ c["dr"]) ** 2 + (xw @ c["di"]) ** 2
    pooled = spec @ c["pool"]
    n_img = (n_fr - fp["img_time"]) // fp["img_hop"] + 1
    iidx = (np.arange(n_img)[:, None] * fp["img_hop"]
            + np.arange(fp["img_time"])[None, :])
    f, t = fp["img_freq"], fp["img_time"]
    imgs = np.ascontiguousarray(np.swapaxes(pooled[iidx], 1, 2))
    a = (imgs.reshape(-1, t) @ c["ht"].T).reshape(n_img, f, t)
    coef = (c["hf"] @ a.transpose(1, 0, 2).reshape(f, -1)).reshape(
        f, n_img, t).transpose(1, 0, 2)
    return coef.reshape(n_img, -1).astype(np.float32)


def frozen_stats(fp: dict, x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Per-coefficient median and MAD over the fingerprints of ``x``."""
    coef = coefficients_np(fp, x)
    med = np.median(coef, axis=0)
    mad = np.median(np.abs(coef - med[None, :]), axis=0)
    return med.astype(np.float32), mad.astype(np.float32)


@functools.lru_cache(maxsize=4)
def _fingerprint_fn(fp_items: tuple, rows: int, precision):
    fp = dict(fp_items)
    c = {k: jnp.asarray(v) for k, v in _constants(fp).items()}
    hop, n = fp["stft_hop"], fp["stft_len"]
    n_fr = (span_samples(fp, rows) - n) // hop + 1
    fidx = np.arange(n_fr)[:, None] * hop + np.arange(n)[None, :]
    iidx = (np.arange(rows)[:, None] * fp["img_hop"]
            + np.arange(fp["img_time"])[None, :])

    def run(x, med, mad):
        xw = x[fidx] * c["win"][None, :]
        re = jnp.matmul(xw, c["dr"], precision=precision)
        im = jnp.matmul(xw, c["di"], precision=precision)
        pooled = jnp.matmul(re * re + im * im, c["pool"],
                            precision=precision)
        imgs = jnp.swapaxes(pooled[iidx], 1, 2)
        coef = jnp.einsum("ij,njk,lk->nil", c["hf"], imgs, c["ht"],
                          precision=precision).reshape(rows, -1)
        z = (coef - med[None, :]) / (mad[None, :] + 1e-9)
        a = jnp.abs(z)
        kth = jax.lax.top_k(a, fp["top_k"])[0][:, -1:]
        keep = a >= kth
        bits = jnp.stack([keep & (z > 0), keep & (z < 0)], axis=-1)
        bits = bits.reshape(rows, -1, 32).astype(jnp.uint32)
        return (bits << jnp.arange(32, dtype=jnp.uint32)).sum(
            axis=-1, dtype=jnp.uint32)

    return jax.jit(run)


def packed_fingerprints(fp: dict, x: np.ndarray, med: np.ndarray,
                        mad: np.ndarray, rows: int = 1024,
                        precision=HIGHEST) -> np.ndarray:
    """Packed fingerprints (N, fp_dim // 32) uint32 of waveform ``x``;
    bit j of word w is fingerprint position 32 w + j, positions 2c and
    2c + 1 the positive and negative sign of coefficient c."""
    n_fp = n_fingerprints(fp, x.shape[-1])
    fn = _fingerprint_fn(tuple(sorted(fp.items())), rows, precision)
    lag = lag_samples(fp)
    need = span_samples(fp, rows)
    med, mad = jnp.asarray(med), jnp.asarray(mad)
    out = []
    for a in range(0, n_fp, rows):
        seg = x[a * lag: a * lag + need]
        seg = np.pad(seg, (0, need - seg.size))
        out.append(np.asarray(fn(jnp.asarray(seg), med, mad)))
    return np.concatenate(out)[:n_fp]


def jaccard(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Exact Jaccard of row-aligned packed fingerprints, float32."""
    inter = np.bitwise_count(a & b).sum(axis=-1, dtype=np.int64)
    union = np.bitwise_count(a | b).sum(axis=-1, dtype=np.int64)
    return np.where(union > 0, inter.astype(np.float32)
                    / np.maximum(union, 1).astype(np.float32),
                    np.float32(0.0)).astype(np.float32)


# ---------------------------------------------------------------------------
# Min-Max LSH signatures and bucket addresses
# ---------------------------------------------------------------------------


def _mix32(x):
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 13)
    x = x * _M2
    return x ^ (x >> 16)


def _hash_u32(x, seed: int):
    return _mix32(x.astype(jnp.uint32) + jnp.uint32(seed) * _GOLDEN)


def _combine(a, b):
    return a ^ (b + _GOLDEN + (a << 6) + (a >> 2))


@functools.lru_cache(maxsize=4)
def _signature_fn(lsh_items: tuple, fp_dim: int, n_buckets: int):
    lsh = dict(lsh_items)
    t = lsh["n_tables"]
    f = lsh["n_funcs"] // 2
    seed = lsh["seed"]
    dims = jnp.arange(fp_dim, dtype=jnp.uint32)[:, None]
    fns = jnp.arange(t * f, dtype=jnp.uint32)[None, :]
    maps = (_mix32(_combine(_hash_u32(dims, seed),
                            _hash_u32(fns, seed ^ 0xABCD))) >> 1
            ).astype(jnp.int32)                                # (D, t f)
    salts = _hash_u32(jnp.arange(t, dtype=jnp.uint32), seed ^ 0xB0C4E7)

    def run(packed):
        n = packed.shape[0]
        bits = ((packed[:, :, None] >> jnp.arange(32, dtype=jnp.uint32))
                & 1).reshape(n, fp_dim).astype(bool)
        big = jnp.int32(2**31 - 1)
        mins = jnp.where(bits[:, :, None], maps[None], big).min(axis=1)
        maxs = jnp.where(bits[:, :, None], maps[None], 0).max(axis=1)
        per_fn = _combine(mins.astype(jnp.uint32).reshape(n, t, f),
                          maxs.astype(jnp.uint32).reshape(n, t, f))
        sig = jnp.zeros((n, t), jnp.uint32)
        for j in range(f):
            sig = _combine(sig, per_fn[:, :, j])
        bkt = (_combine(sig, salts[None, :])
               & jnp.uint32(n_buckets - 1)).astype(jnp.int32)
        return sig, bkt

    return jax.jit(run)


def signatures(lsh: dict, packed: np.ndarray, fp_dim: int, n_buckets: int,
               rows: int = 256) -> tuple[np.ndarray, np.ndarray]:
    """(signatures (N, t) uint32, bucket ids (N, t) int32)."""
    if not lsh["use_minmax"]:
        raise ValueError("the reference implements Min-Max hashing only")
    fn = _signature_fn(tuple(sorted(lsh.items())), fp_dim, n_buckets)
    sigs, bkts = [], []
    n = packed.shape[0]
    for a in range(0, n, rows):
        blk = packed[a:a + rows]
        blk = np.pad(blk, ((0, rows - blk.shape[0]), (0, 0)))
        s, b = fn(jnp.asarray(blk))
        sigs.append(np.asarray(s))
        bkts.append(np.asarray(b))
    return np.concatenate(sigs)[:n], np.concatenate(bkts)[:n]


# ---------------------------------------------------------------------------
# the resident index's pair emission
# ---------------------------------------------------------------------------


def index_pairs(sig: np.ndarray, bkt: np.ndarray, block: int, cap: int,
                min_dt: int, n_matches: int, saturation: int,
                occ_limit: int, max_pairs: int):
    """Pairs the index emits for fingerprints 0..N-1 streamed in blocks
    of ``block`` ids from an empty index.

    Returns (idx1, idx2, sim, overflow per block): the emitted pairs in
    block order. Assumes the stream is shorter than the detection window
    and the occurrence ring, so nothing expires and no count is recycled.
    """
    n, t = sig.shape
    ids = np.arange(n, dtype=np.int64)
    batch_end = (ids // block + 1) * block
    hit_q, hit_p, hit_ok = [], [], []
    for tb in range(t):
        order = np.lexsort((ids, bkt[:, tb]))
        sb = bkt[order, tb].astype(np.int64)
        si = ids[order]
        ss = sig[order, tb]
        key = sb << 32 | si
        first = np.r_[True, sb[1:] != sb[:-1]]
        run_start = np.maximum.accumulate(np.where(first, np.arange(n), 0))
        # the bucket at query time holds its ``cap`` newest entries among
        # the ids inserted so far (through the end of the query's block)
        q_end = np.searchsorted(key, sb << 32 | batch_end[si], "left") - 1
        traffic = q_end - run_start + 1
        lo_pos = np.maximum(run_start, q_end - cap + 1)
        pos = np.arange(n)
        for w in range(1, cap + 1):
            cand = pos - w
            ok = cand >= lo_pos
            c = np.where(ok, cand, 0)
            ok &= (ss[c] == ss) & (si - si[c] >= min_dt)
            q = si[ok]
            hit_q.append(q)
            hit_p.append(si[c[ok]])
            hit_ok.append(traffic[ok] <= saturation if saturation > 0
                          else np.ones(q.size, bool))
    hq = np.concatenate(hit_q)
    hp = np.concatenate(hit_p)
    hok = np.concatenate(hit_ok)
    # m-of-t: tables in which the pair's hit survived the quarantine
    pk = hp[hok] * n + hq[hok]
    uniq, sim = np.unique(pk, return_counts=True)
    keep = sim >= n_matches
    uniq, sim = uniq[keep], sim[keep]
    p1, p2 = uniq // n, uniq % n
    # occurrence limiter: raw collisions (before the quarantine) count
    # against both endpoints, block by block in stream order
    occ = np.zeros(n, np.int64)
    qb = hq // block
    pb = p2 // block
    n_blocks = -(-n // block)
    out = []
    overflow = np.zeros(n_blocks, np.int64)
    h_order = np.argsort(qb, kind="stable")
    h_bounds = np.searchsorted(qb[h_order], np.arange(n_blocks + 1))
    p_order = np.argsort(pb, kind="stable")
    p_bounds = np.searchsorted(pb[p_order], np.arange(n_blocks + 1))
    for b in range(n_blocks):
        hs = h_order[h_bounds[b]:h_bounds[b + 1]]
        if occ_limit > 0 and hs.size:
            np.add.at(occ, hq[hs], 1)
            np.add.at(occ, hp[hs], 1)
        ps = p_order[p_bounds[b]:p_bounds[b + 1]]
        a1, a2, s = p1[ps], p2[ps], sim[ps]
        if occ_limit > 0:
            live = (occ[a1] <= occ_limit) & (occ[a2] <= occ_limit)
            a1, a2, s = a1[live], a2[live], s[live]
        o = np.lexsort((a2, a1))
        if max_pairs > 0 and o.size > max_pairs:
            overflow[b] = o.size - max_pairs
            o = o[:max_pairs]
        out.append((a1[o], a2[o], s[o]))
    if not out:
        empty = np.zeros(0, np.int64)
        return empty, empty, empty, overflow
    o1, o2, os_ = (np.concatenate(c) for c in zip(*out))
    return o1, o2, os_, overflow
