"""The system under test, built from a configuration file.

Everything the benchmark takes from the program goes through here: the
configuration dataclasses (every field set from the file, none left to
the program's defaults), the ``StreamingDetector`` with its frozen
per-station statistics, the lower matmul precision of the control runs,
and the taps on what the timed path produces (each step's verify
Jaccard values, and each checked station's pairs as the host receives
them). The taps hold references and copy a few hundred values per step;
they add no device work and no transfer.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

PRECISIONS = {"highest": jax.lax.Precision.HIGHEST,
              "high": jax.lax.Precision.HIGH}


def build(conf: dict):
    """(DetectConfig, StreamConfig) with every field from ``conf``."""
    from repro.core import AlignConfig, DetectConfig, FingerprintConfig, \
        LSHConfig
    from repro.stream.index import StreamIndexConfig
    from repro.stream.ingest import StreamConfig
    cfg = DetectConfig(fingerprint=FingerprintConfig(**conf["fingerprint"]),
                       lsh=LSHConfig(**conf["lsh"]),
                       align=AlignConfig(**conf["align"]))
    stream = dict(conf["stream"])
    stream["index"] = StreamIndexConfig(**conf["index"])
    return cfg, StreamConfig(**stream)


def set_precision(name: str) -> None:
    """Matmul precision of the program's fingerprint chain (the control
    runs lower it; the configuration states HIGHEST). The precision is
    read when a step is traced, so programs already traced in this
    process are dropped."""
    from repro.core import fingerprint
    from repro.kernels import ref
    ref.MATMUL_PRECISION = fingerprint.MATMUL_PRECISION = PRECISIONS[name]
    jax.clear_caches()


def make_detector(cfg, scfg, med: np.ndarray, mad: np.ndarray):
    """A pooled ``StreamingDetector`` with frozen per-station statistics,
    its pool state on the chips.

    The detector is assembled under the host CPU device: its per-station
    index states and their stacked pool pass through host memory, and the
    pool then goes to the chips once (to the mesh shards when there is a
    mesh). Built on the chip, the per-station states and the stacked pool
    would both sit on the first chip at once, twice the pool's size,
    which a chip's share of the deployment does not leave room for.
    """
    from repro.stream.engine import StreamingDetector
    with jax.default_device(jax.devices("cpu")[0]):
        det = StreamingDetector(cfg, scfg, n_stations=med.shape[0],
                                med_mad=(med, mad))
    if det.mesh is None:
        dev = jax.devices()[0]
        det.pstate = jax.device_put(det.pstate, dev)
        det._pool_mappings = jax.device_put(det._pool_mappings, dev)
    jax.block_until_ready(det.pstate)
    return det


@dataclasses.dataclass
class Taps:
    """What the timed path produced, held for the check after the window."""

    jac: list = dataclasses.field(default_factory=list)   # device (S, P)
    pairs: dict = dataclasses.field(default_factory=dict)  # station -> rows

    def install(self, det, stations, annotate) -> None:
        from repro.stream import fused
        taps = self

        def tap_entry(fn):
            def entry(*args, **kwargs):
                with annotate("bench.dispatch"):
                    state, pairs, qc = fn(*args, **kwargs)
                taps.jac.append(pairs.jac)
                return state, pairs, qc
            entry.__wrapped__ = fn
            return entry

        for name in ("pool_step_advance_sharded", "pool_step_block_sharded"):
            setattr(fused, name, tap_entry(getattr(fused, name)))
        for s in stations:
            st = det.stations[s]
            rows = self.pairs.setdefault(int(s), [])

            def consume(base_id, n_adv, n_valid, pairs_np, _orig=st._consume,
                        _rows=rows):
                i1, i2, sim, pv = pairs_np
                pos = np.flatnonzero(pv)
                _rows.append((len(taps.jac) - 1, pos, i1[pos].copy(),
                              i2[pos].copy(), sim[pos].copy()))
                return _orig(base_id, n_adv, n_valid, pairs_np)

            st._consume = consume

    @staticmethod
    def uninstall() -> None:
        from repro.stream import fused
        for name in ("pool_step_advance_sharded", "pool_step_block_sharded"):
            fn = getattr(fused, name)
            setattr(fused, name, getattr(fn, "__wrapped__", fn))
