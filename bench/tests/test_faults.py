"""A whole run, the chip check skipped, at a CPU size: sound, it is
correct; with the timed path broken underneath, ``correct`` is false.

The faults the cells can have, each planted around the program's pool
step entries (under the benchmark's taps, so the step's own output is
what changes):

* the step returns its state unchanged (nothing is inserted, no
  fingerprint reaches the verify ring);
* half of each block left out (the pairs of every odd query id dropped);
* one station of the pool left out (its pairs never reach the host);
* an answer altered where it is produced (each pair's table count + 1);
* the verify epilogue's answer computed one precision lower (each exact
  Jaccard value rounded to bfloat16), which only ``jaccard_err`` sees;
* the exchange between chips left out: on a four-device station mesh
  (four host devices here), only the first device's stations come back
  from the step; the others' pairs never reach the host.

The sharded step has no collective: what crosses chips is the per-shard
input put and the station-sharded pull, which the last fault breaks.
"""
import dataclasses
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness, traffic

DATA = pathlib.Path(__file__).resolve().parent / "data"
CELL = {"name": "tiny", "chips": 1}
MESH_CELL = {"name": "tiny4", "chips": 4}
SEED = 2**31 + 977
WORKLOAD = "paper_backfill.quiet"   # whose metrics and limits a run uses


def _run(fault=None, cell=CELL):
    conf = json.loads((DATA / "tiny_config.json").read_text())
    if cell["chips"] > 1:
        conf["stations"] = cell["chips"]
    mix = traffic.load_mix("tiny", DATA)
    spec = harness.load_spec()
    metrics = harness.metrics_of(spec, WORKLOAD, False)
    return harness.run_cell(cell, conf, mix, SEED, 1.5, False, metrics,
                            harness.limits_for(WORKLOAD),
                            t_start=time.perf_counter(), require_tpu=False,
                            fault=fault)


def _wrap(make):
    from repro.stream import fused

    def plant():
        for name in ("pool_step_advance_sharded", "pool_step_block_sharded"):
            inner = getattr(fused, name)
            outer = make(inner)
            outer.__wrapped__ = inner
            setattr(fused, name, outer)
    return plant


def _unchanged(inner):
    def entry(state, *args, **kwargs):
        _, pairs, qc = inner(jax.tree.map(jnp.copy, state), *args, **kwargs)
        return state, pairs, qc
    return entry


def _half(inner):
    def entry(*args, **kwargs):
        state, pairs, qc = inner(*args, **kwargs)
        keep = pairs.valid & (pairs.idx2 % 2 == 0)
        return state, dataclasses.replace(pairs, valid=keep), qc
    return entry


def _one_station(inner):
    def entry(*args, **kwargs):
        state, pairs, qc = inner(*args, **kwargs)
        keep = pairs.valid.at[1].set(False)
        return state, dataclasses.replace(pairs, valid=keep), qc
    return entry


def _altered(inner):
    def entry(*args, **kwargs):
        state, pairs, qc = inner(*args, **kwargs)
        sim = jnp.where(pairs.valid, pairs.sim + 1, pairs.sim)
        return state, dataclasses.replace(pairs, sim=sim), qc
    return entry


def _jaccard_bf16(inner):
    def entry(*args, **kwargs):
        state, pairs, qc = inner(*args, **kwargs)
        jac = pairs.jac.astype(jnp.bfloat16).astype(pairs.jac.dtype)
        return state, dataclasses.replace(pairs, jac=jac), qc
    return entry


def _first_shard_only(inner):
    def entry(*args, mesh=None, **kwargs):
        state, pairs, qc = inner(*args, mesh=mesh, **kwargs)
        assert mesh is not None and mesh.devices.size > 1
        rows = pairs.valid.shape[0] // mesh.devices.size
        first = jnp.arange(pairs.valid.shape[0]) < rows
        keep = pairs.valid & first.reshape((-1,) + (1,) * (
            pairs.valid.ndim - 1))
        return state, dataclasses.replace(pairs, valid=keep), qc
    return entry


def _restore():
    from repro.stream import fused
    for name in ("pool_step_advance_sharded", "pool_step_block_sharded"):
        fn = getattr(fused, name)
        while hasattr(fn, "__wrapped__"):
            fn = fn.__wrapped__
        setattr(fused, name, fn)


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"backfill_rate", "setup_s"}
    assert list(res)[-1] == "checks"


def test_sound_sharded_run_is_correct():
    assert len(jax.devices()) >= MESH_CELL["chips"]
    res = _run(cell=MESH_CELL)
    assert res["correct"], res["checks"]


# each fault, the number that has to catch it, and the cell it runs in
FAULTS = [
    (_unchanged, "pair_mismatch", CELL),
    (_half, "pair_mismatch", CELL),
    (_one_station, "pair_mismatch", CELL),
    (_altered, "pair_mismatch", CELL),
    (_jaccard_bf16, "jaccard_err", CELL),
    (_first_shard_only, "pair_mismatch", MESH_CELL),
]


@pytest.mark.parametrize("make,number,cell", FAULTS,
                         ids=["state_unchanged", "half_left_out",
                              "one_station_left_out", "answer_altered", "jaccard_bf16",
                              "exchange_left_out"])
def test_fault_is_caught(make, number, cell):
    try:
        res = _run(_wrap(make), cell=cell)
    finally:
        _restore()
    print({k: c["value"] for k, c in res["checks"].items()})
    assert not res["correct"], res["checks"]
    assert res["checks"][number]["value"] > res["checks"][number]["limit"]
