"""The default stream layout: every component of every station is a pool
member of its own.

A station records ``channels`` components (the configuration's key,
default 1). Each becomes one stream of the program's station pool, in
station-major and component-minor order, fingerprinted, hashed and
indexed on its own: the paper's per-channel stages (arXiv:1803.09835
§5–§6). The check compares each stream's fingerprints and pairs with the
plain reference (``bench/reference.py``); nothing downstream of the
pairs, such as a merge of a station's channels, is checked here.
"""
from __future__ import annotations

import jax
import numpy as np

from bench import program, reference, traffic
from bench.harness import log


def streams(conf: dict) -> np.ndarray:
    """The station of each pool stream, station-major, component-minor."""
    return np.repeat(np.arange(int(conf["stations"])),
                     int(conf.get("channels", 1)))


def make_stream(conf: dict, mix, seed: int, lag: int,
                chunk_samples: int) -> traffic.NetworkStream:
    return traffic.NetworkStream(mix, int(conf["stations"]), seed,
                                 conf["fingerprint"]["fs"], lag,
                                 chunk_samples,
                                 channels=int(conf.get("channels", 1)))


def frozen_stats(conf: dict, mix, stream) -> tuple[np.ndarray, np.ndarray]:
    """(median, MAD) per stream over the first ``stats_fingerprints``."""
    fp = conf["fingerprint"]
    first = stream.span(reference.span_samples(fp, mix.stats_fingerprints))
    stats = [reference.frozen_stats(fp, x) for x in first]
    return (np.stack([s[0] for s in stats]), np.stack([s[1] for s in stats]))


def make_detector(cfg, scfg, med: np.ndarray, mad: np.ndarray):
    return program.make_detector(cfg, scfg, med, mad)


def install_taps(det, annotate) -> program.Taps:
    taps = program.Taps()
    taps.install(det, range(len(det.stations)), annotate)
    return taps


def processed(det) -> list[int]:
    """Fingerprints each stream has put through the step."""
    return [st.processed_fp for st in det.stations]


def compare(conf: dict, stream, stats, n_fp: list, pk_rows: list, taps,
            overflow: int, limits: dict) -> dict:
    """The window's output against the plain reference, stream by stream
    over the whole pool; each number is the worst stream's, beside its
    limit."""
    fp, lsh, idx, st = (conf["fingerprint"], conf["lsh"], conf["index"],
                        conf["stream"])
    fp_dim = 2 * fp["img_freq"] * fp["img_time"]
    x_all = stream.span(reference.span_samples(fp, max(n_fp)))
    jac_host: dict = {}
    fp_worst = pair_worst = jac_err = 0.0
    ref_total = 0
    for s, n in enumerate(n_fp):
        if n >= st["window_fingerprints"] > 0 or n > idx["pk_slots"]:
            raise ValueError("the stream outgrew the detection window; the "
                             "reference assumes nothing expired")
        x = x_all[s, :reference.span_samples(fp, n)]
        ref_pk = reference.packed_fingerprints(fp, x, stats[0][s],
                                               stats[1][s])
        same_fp = (ref_pk == pk_rows[s]).all(axis=1)
        fp_worst = max(fp_worst, float(n - same_fp.sum()) / max(1, n))
        sig, bkt = reference.signatures(lsh, ref_pk, fp_dim,
                                        idx["n_buckets"])
        r1, r2, rsim, _ = reference.index_pairs(
            sig, bkt, st["block_fingerprints"], idx["bucket_cap"],
            lsh["min_dt"], lsh["n_matches"], st["saturation_limit"],
            st["occ_limit"], st["max_pairs_per_block"])
        rows = taps.pairs[s]
        for k, *_ in rows:
            if k not in jac_host:
                jac_host[k] = np.asarray(jax.device_get(taps.jac[k]))
        g1 = np.concatenate([r[2] for r in rows] + [np.zeros(0, int)])
        g2 = np.concatenate([r[3] for r in rows] + [np.zeros(0, int)])
        gsim = np.concatenate([r[4] for r in rows] + [np.zeros(0, int)])
        gjac = np.concatenate([jac_host[r[0]][s, r[1]] for r in rows]
                              + [np.zeros(0, np.float32)])
        # a pair is (idx1, idx2, table count); a pair streamed twice is
        # a difference too
        rkey = (r1.astype(np.int64) * n + r2) * 256 + rsim
        gkey = (g1.astype(np.int64) * n + g2) * 256 + gsim
        diff = (np.setxor1d(rkey, gkey).size
                + gkey.size - np.unique(gkey).size)
        ref_total += rkey.size
        pair_worst = max(pair_worst, diff / rkey.size if rkey.size
                         else float(diff > 0))
        # the verify epilogue alone: pairs both sides emitted, between
        # fingerprints whose bits agree with the reference
        _, ri, gi = np.intersect1d(r1.astype(np.int64) * n + r2,
                                   g1.astype(np.int64) * n + g2,
                                   return_indices=True)
        ok = same_fp[r1[ri]] & same_fp[r2[ri]]
        ri, gi = ri[ok], gi[ok]
        if ri.size:
            rj = reference.jaccard(ref_pk[r1[ri]], ref_pk[r2[ri]])
            jac_err = max(jac_err, float(np.abs(rj - gjac[gi]).max()))
        log(f"stream {s}: {n} fingerprints, {n - int(same_fp.sum())} "
            f"differ; {rkey.size} reference pairs, {gkey.size} streamed, "
            f"{diff} different")
    values = {
        "fp_mismatch": fp_worst,
        "pair_mismatch": pair_worst if ref_total else 1.0,
        "jaccard_err": jac_err,
        "overflow": float(overflow),
    }
    return {name: {"value": v, "limit": float(limits[name])}
            for name, v in values.items()}
