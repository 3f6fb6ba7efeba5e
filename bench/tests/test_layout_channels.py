"""The three-component layout (``bench/layouts/station_channels.py``) at a
CPU size: a sound run is correct and its check sees every station's
filter-window closes; each fault planted in the program's channel merge
fails ``merge_mismatch`` above its limit:

* one component's triplets left out of the merge;
* the threshold applied per channel, not on the channels' sum;
* the occurrence filter skipped before the merge.
"""
import json
import time

import numpy as np
import pytest

from bench import harness, traffic
from bench.tests.test_faults import CELL, DATA, SEED

WORKLOAD = "paper_backfill_3c.swarm"   # whose metrics and limits a run uses


def _conf():
    conf = json.loads((DATA / "tiny_config.json").read_text())
    conf["stream"]["filter_window_fingerprints"] = 64
    return dict(conf, channels=3,
                layout="bench/layouts/station_channels.py")


def _run(fault=None):
    spec = harness.load_spec()
    return harness.run_cell(CELL, _conf(), traffic.load_mix("tiny", DATA),
                            SEED, 1.5, False,
                            harness.metrics_of(spec, WORKLOAD, False),
                            harness.limits_for(WORKLOAD),
                            t_start=time.perf_counter(), require_tpu=False,
                            fault=fault)


def _plant(module, name, make):
    """A fault: ``module.name`` replaced by ``make(inner)`` while a run
    sets up (before the taps wrap it); returns (plant, restore)."""
    inner = getattr(module, name)

    def plant():
        setattr(module, name, make(inner))

    def restore():
        setattr(module, name, inner)
    return plant, restore


def _drop_component(inner):
    return lambda tris, threshold: inner(tris[:1] + tris[2:], threshold)


def _threshold_per_channel(inner):
    return lambda tris, threshold: inner(
        [inner([t], threshold) for t in tris], threshold)


def _no_occurrence_filter(inner):
    return lambda idx1, *args, **kwargs: np.ones(idx1.size, bool)


def test_sound_run_is_correct_and_closes_every_station(capsys):
    res = _run()
    seen = [ln[2:] for ln in capsys.readouterr().err.splitlines()
            if ln.startswith("# ")]
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == {"fp_mismatch", "pair_mismatch",
                                  "jaccard_err", "overflow",
                                  "merge_mismatch"}
    assert res["checks"]["merge_mismatch"]["value"] == 0.0
    stations = [m for m in seen if m.startswith("station ")]
    assert len(stations) == 3
    for m in stations:
        # every station closed a window and merged something in it
        assert "the program merged in []" not in m, m
        assert " 0 reference merged" not in m, m


def _faults():
    from repro.core import align, lsh
    return [(align, "merge_channels_host", _drop_component),
            (align, "merge_channels_host", _threshold_per_channel),
            (lsh, "occurrence_keep", _no_occurrence_filter)]


@pytest.mark.parametrize("k", range(3), ids=["component_left_out",
                                             "threshold_per_channel",
                                             "occurrence_filter_skipped"])
def test_merge_fault_is_caught(k):
    plant, restore = _plant(*_faults()[k])
    try:
        res = _run(plant)
    finally:
        restore()
    print({name: c["value"] for name, c in res["checks"].items()})
    check = res["checks"]["merge_mismatch"]
    assert not res["correct"], res["checks"]
    assert check["value"] > check["limit"]
