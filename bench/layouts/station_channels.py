"""A layout of three-component stations whose channels merge per station.

Every component of every station is a pool member of its own, in
station-major and component-minor order, fingerprinted, hashed and
indexed on its own, as in the default layout (``network.py``). The
detector is built with ``channels``, so each station's rolling filter
merges its components (arXiv:1803.09835 §7.1, channel level): at each
filter-window close, the §6.5 occurrence filter runs per component over
the window's pairs, the similarity of each (dt, idx1) is summed over the
station's components and the sum thresholded (``channel_threshold``),
and the merged triplets are clustered.

The check keeps the default layout's four per-stream numbers on every
stream (``network.compare``) and adds ``merge_mismatch``: per station,
the symmetric difference between the merged (idx1, idx2, summed sim)
triplets of the closes that ran in the timed path and this module's own
numpy copy of the reference, divided by the reference's count; the worst
station's. For each filter window the program closed, the copy takes
each component's reference pairs (``bench/reference.py``) assigned to
the window of their later member, applies the occurrence filter per
component over the window's rebased span, then sums and thresholds.
Nothing here imports the program, apart from building the detector.
"""
from __future__ import annotations

import numpy as np

from bench import reference
from bench.harness import log
from bench.layouts import network
from bench.layouts.network import install_taps as _stream_taps
from bench.layouts.network import make_stream, processed, streams  # noqa: F401


def frozen_stats(conf: dict, mix, stream) -> tuple[np.ndarray, np.ndarray]:
    """(median, MAD) per stream, shaped (stations, channels, n_coeff)."""
    med, mad = network.frozen_stats(conf, mix, stream)
    shape = (int(conf["stations"]), int(conf["channels"]), -1)
    return med.reshape(shape), mad.reshape(shape)


def make_detector(cfg, scfg, med: np.ndarray, mad: np.ndarray):
    """The pooled ``StreamingDetector`` of ``med.shape[0]`` stations with
    ``med.shape[1]`` components each, assembled under the host CPU device
    and its pool put on the chip once, as ``program.make_detector`` does
    for one-component stations."""
    import jax
    from repro.stream.engine import StreamingDetector
    n_st, channels = med.shape[:2]
    with jax.default_device(jax.devices("cpu")[0]):
        det = StreamingDetector(cfg, scfg, n_stations=n_st,
                                channels=channels,
                                med_mad=(med.reshape(n_st * channels, -1),
                                         mad.reshape(n_st * channels, -1)))
    if det.mesh is None:
        dev = jax.devices()[0]
        det.pstate = jax.device_put(det.pstate, dev)
        det._pool_mappings = jax.device_put(det._pool_mappings, dev)
    jax.block_until_ready(det.pstate)
    return det


class _MergeTap:
    """Each station's merged triplets, close by close, as the program's
    channel merge returned them: {station: [(window start, (k, 3))]}."""

    def __init__(self):
        self.merged: dict[int, list] = {}
        self._open: list | None = None
        self._align = None

    def install(self, det) -> None:
        from repro.core import align
        self.uninstall()
        self._align = align
        inner = align.merge_channels_host
        tap = self

        def merge(tris, threshold):
            out = inner(tris, threshold)
            if tap._open is not None:
                tap._open.append(np.array(out, np.int64))
            return out

        merge.__wrapped__ = inner
        align.merge_channels_host = merge
        for s, filt in enumerate(det.filters):
            rows = self.merged.setdefault(s, [])

            def close(tris, _orig=filt._filter_cluster, _rows=rows,
                      _filt=filt):
                tap._open = []
                try:
                    return _orig(tris)
                finally:
                    for m in tap._open:
                        _rows.append((_filt.w_start, m))
                    tap._open = None

            filt._filter_cluster = close

    def uninstall(self) -> None:
        if self._align is not None:
            fn = self._align.merge_channels_host
            self._align.merge_channels_host = getattr(fn, "__wrapped__", fn)
            self._align = None


def install_taps(det, annotate):
    taps = _stream_taps(det, annotate)
    taps.merge = _MergeTap()
    taps.merge.install(det)
    return taps


# ---------------------------------------------------------------------------
# the merge's reference, in numpy
# ---------------------------------------------------------------------------


def occurrence_keep(i1: np.ndarray, i2: np.ndarray, n: int,
                    limit: int) -> np.ndarray:
    """§6.5: a fingerprint in more than ``limit`` of the pairs, and every
    fingerprint paired with one, is excluded; the pairs between two kept
    fingerprints survive. Ids lie in [0, n)."""
    cnt = np.bincount(i1, minlength=n) + np.bincount(i2, minlength=n)
    over = cnt > limit
    out = over.copy()
    out[i1[over[i2]]] = True
    out[i2[over[i1]]] = True
    return ~out[i1] & ~out[i2]


def channel_sum(tris: list, threshold: int) -> np.ndarray:
    """(idx1, idx2, sim) per component → (idx1, idx2, summed sim) of each
    (dt, idx1) whose sum over the components reaches ``threshold``."""
    tri = np.concatenate(tris)
    if not tri.shape[0]:
        return tri.reshape(0, 3)
    keys, inv = np.unique(tri[:, :2], axis=0, return_inverse=True)
    tot = np.bincount(inv.reshape(-1), weights=tri[:, 2]).astype(np.int64)
    keep = tot >= threshold
    return np.concatenate([keys[keep], tot[keep, None]], axis=1)


def merged_reference(comps: list, n_fp: int, window: int, lookback: int,
                     frac: float, threshold: int) -> list:
    """One station's merged triplets for each filter window closed by
    fingerprint ``n_fp``: [(window start, (k, 3))]."""
    out = []
    limit = max(1, int(frac * window))
    for start in range(0, n_fp - window + 1, window):
        base = start - lookback
        cur = [t[(t[:, 1] >= start) & (t[:, 1] < start + window)]
               for t in comps]
        if frac > 0:
            cur = [t[occurrence_keep(t[:, 0] - base, t[:, 1] - base,
                                     lookback + window, limit)]
                   for t in cur]
        out.append((start, channel_sum(cur, threshold)))
    return out


def _key(tri: np.ndarray, n: int) -> np.ndarray:
    return (tri[:, 0].astype(np.int64) * n + tri[:, 1]) * 4096 + tri[:, 2]


def compare(conf: dict, stream, stats, n_fp: list, pk_rows: list, taps,
            overflow: int, limits: dict) -> dict:
    """The default layout's per-stream checks on every stream, and the
    per-station merge check (``merge_mismatch``), each beside its
    limit."""
    taps.merge.uninstall()
    n_st, channels = int(conf["stations"]), int(conf["channels"])
    # each stream's reference pairs, as the per-stream check makes them
    pairs = []
    inner = reference.index_pairs

    def index_pairs(*args, **kwargs):
        out = inner(*args, **kwargs)
        pairs.append(np.stack(out[:3], axis=1).astype(np.int64))
        return out

    reference.index_pairs = index_pairs
    try:
        checks = network.compare(
            conf, stream, tuple(s.reshape(n_st * channels, -1)
                                for s in stats),
            n_fp, pk_rows, taps, overflow, limits)
    finally:
        reference.index_pairs = inner
    st, lsh, al = conf["stream"], conf["lsh"], conf["align"]
    worst, closes = 0.0, 0
    for s in range(n_st):
        n = n_fp[s * channels]
        ref = merged_reference(
            pairs[s * channels:(s + 1) * channels], n,
            st["filter_window_fingerprints"], st["window_fingerprints"],
            lsh["occurrence_frac"], al["channel_threshold"])
        got = taps.merge.merged.get(s, [])
        rkey = np.concatenate([_key(m, n) for _, m in ref]
                              + [np.zeros(0, np.int64)])
        gkey = np.concatenate([_key(m, n) for _, m in got]
                              + [np.zeros(0, np.int64)])
        diff = (np.setxor1d(rkey, gkey).size
                + gkey.size - np.unique(gkey).size)
        worst = max(worst, diff / rkey.size if rkey.size
                    else float(diff > 0))
        closes += len(ref)
        log(f"station {s}: filter windows closed by fingerprint {n} "
            f"{[w for w, _ in ref]}, the program merged in "
            f"{[w for w, _ in got]}; {rkey.size} reference merged "
            f"triplets, {gkey.size} merged, {diff} different")
    # a run in which no window closed has no merge to check
    checks["merge_mismatch"] = {"value": worst if closes else 1.0,
                                "limit": float(limits["merge_mismatch"])}
    return checks
