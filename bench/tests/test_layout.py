"""The stream layout seam and the generator's components.

* The one-component stream is the stream the benchmark has always run:
  digests of chunks 0-2 of ``quiet`` and ``swarm`` at
  ``fast_paper_backfill``'s 11 stations, recorded before components
  existed, and component 0 of a three-component stream is that stream.
* Components of a station share the event onsets and differ in their
  waveforms.
* A two-component configuration runs end to end through the default
  layout on the CPU, is correct, and its rate counts stations, not
  streams; one component's pairs dropped under the taps fail
  ``pair_mismatch``.
* A configuration's own layout module is the one run; a layout path that
  does not exist fails the set-up.
"""
import dataclasses
import hashlib
import json
import pathlib
import time

import numpy as np
import pytest

from bench import harness, reference, traffic
from bench.tests.test_faults import CELL, DATA, SEED, WORKLOAD, _restore, \
    _wrap

# sha256 of chunks 0, 1 and 2 (float32 bytes, in order) of the stream at
# fast_paper_backfill's shapes: 11 stations, 100 Hz, lag 200 samples,
# chunks of 256 lags
DIGESTS = {
    ("quiet", 7):
        "951eab981601acc84c5b914d83e797df0f197b8fe9bbdf18f375e8bca2e2d73c",
    ("quiet", 2**31 + 555):
        "050a6e38ddfb4edb012fadece47cb42d0bec0b129c6d439407265215c6a5e180",
    ("swarm", 7):
        "56537a6b3f4a5adbb068342b274c53486bc3591a2b41e2c4d08e04ec411226ac",
    ("swarm", 2**31 + 555):
        "31c70480b2ee789d5d6610f713d53ea090f9b26b96107a9a49baeaf488661519",
}


def _paper_stream(mix: str, seed: int, channels: int):
    conf = json.loads((harness.ROOT / "bench/configs/"
                       "fast_paper_backfill.json").read_text())
    fp = conf["fingerprint"]
    lag = reference.lag_samples(fp)
    return traffic.NetworkStream(
        traffic.load_mix(mix), conf["stations"], seed, fp["fs"], lag,
        conf["stream"]["block_fingerprints"] * lag, channels=channels)


@pytest.mark.parametrize("channels", [1, 3])
@pytest.mark.parametrize("mix,seed", sorted(DIGESTS))
def test_component_zero_is_the_recorded_stream(mix, seed, channels):
    stream = _paper_stream(mix, seed, channels)
    h = hashlib.sha256()
    for k in range(3):
        chunk = stream.chunk(k)
        assert chunk.shape == (11 * channels, stream.chunk_samples)
        h.update(np.ascontiguousarray(chunk[::channels]).tobytes())
    assert h.hexdigest() == DIGESTS[mix, seed]


def test_components_share_onsets_not_waveforms():
    channels = 3
    stream = traffic.NetworkStream(traffic.load_mix("swarm"), 2, 2**31 + 9,
                                   100.0, 200, 51200, channels=channels)
    chunk = stream.chunk(0).reshape(2, channels, -1)
    noise = np.stack([np.random.default_rng(
        [stream.seed, 1, 0] + ([c] if c else [])).standard_normal(
            (2, 51200), dtype=np.float32) for c in range(channels)], axis=1)
    # the noise is made again exactly, so what is left is the events alone
    events = chunk - noise * np.float32(stream.mix.noise_sigma)
    for st in range(2):
        support = events[st] != 0
        assert support[0].sum() > 0
        assert (support == support[0]).all()
        for c in range(1, channels):
            assert not np.allclose(events[st, c], events[st, 0])


def _tiny(channels=1, **extra):
    conf = json.loads((DATA / "tiny_config.json").read_text())
    return dict(conf, channels=channels, **extra)


def _run(conf, fault=None):
    spec = harness.load_spec()
    return harness.run_cell(CELL, conf, traffic.load_mix("tiny", DATA), SEED,
                            1.5, False, harness.metrics_of(spec, WORKLOAD,
                                                           False),
                            harness.limits_for(WORKLOAD),
                            t_start=time.perf_counter(), require_tpu=False,
                            fault=fault)


def test_two_components_run_and_count_stations(monkeypatch):
    seen = []
    real = harness.reader

    def reader(name):
        def read(ctx):
            seen.append(ctx)
            return real(name)(ctx)
        return read

    monkeypatch.setattr(harness, "reader", reader)
    conf = _tiny(channels=2)
    res = _run(conf)
    assert res["correct"], res["checks"]
    ctx = seen[0]
    fp = conf["fingerprint"]
    block_s = (conf["stream"]["block_fingerprints"]
               * reference.lag_samples(fp) / fp["fs"])
    assert ctx["blocks"] > 0
    assert ctx["stations"] == conf["stations"] == 3
    assert ctx["station_s"] == 3 * ctx["blocks"] * block_s
    assert res["metrics"]["backfill_rate"]["value"] == pytest.approx(
        ctx["station_s"] / 3600 / ctx["window_s"])


def _drop_component(inner):
    """Pool member 1, the second component of station 0, loses every
    pair."""
    def entry(*args, **kwargs):
        state, pairs, qc = inner(*args, **kwargs)
        keep = pairs.valid.at[1].set(False)
        return state, dataclasses.replace(pairs, valid=keep), qc
    return entry


def test_dropped_component_is_caught():
    try:
        res = _run(_tiny(channels=2), _wrap(_drop_component))
    finally:
        _restore()
    assert not res["correct"], res["checks"]
    check = res["checks"]["pair_mismatch"]
    assert check["value"] > check["limit"]


def test_own_layout_is_the_one_run(tmp_path):
    own = tmp_path / "own_layout.py"
    own.write_text(
        "from bench.layouts.network import *  # noqa: F401,F403\n"
        "from bench.layouts import network\n\n\n"
        "def compare(*args, **kwargs):\n"
        "    checks = network.compare(*args, **kwargs)\n"
        "    checks['own_layout'] = {'value': 0.0, 'limit': 0.0}\n"
        "    return checks\n")
    res = _run(_tiny(layout=str(own)))
    assert res["correct"], res["checks"]
    assert "own_layout" in res["checks"]


def test_missing_layout_fails_the_setup():
    with pytest.raises(FileNotFoundError, match="bench/layouts/no_such.py"):
        _run(_tiny(layout="bench/layouts/no_such.py"))
