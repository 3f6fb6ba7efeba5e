"""Compile the detection kernels for a described TPU v5e at the paper's
widths, with no chip attached.

Interpret-mode parity (``test_kernels.py``) cannot see a block that
breaks the (8, 128) tiling or a kernel body that overruns fast memory;
the TPU compiler can. Each case goes through ``kernels/ops.py``'s padding
with the kernel compiled rather than interpreted, and must still contain
its Mosaic custom call — a kernel that quietly became XLA fails.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.fast_seismic import config, stream_config
from repro.kernels import ops
from repro.stream import index as SI


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_chip(one_chip, monkeypatch):
    """jit → lower → compile for the described chip, with the Pallas
    kernels compiled (the CPU default would interpret them) and the
    persistent cache off: a TPU executable written there could not be
    read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def compile_(fn, *shapes, donate=()):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn, donate_argnums=donate).lower(*args).compile()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _paper_cases():
    cfg, scfg = config(), stream_config()
    fcfg, lcfg = cfg.fingerprint, cfg.lsh
    n, d = scfg.block_fingerprints, fcfg.fp_dim
    lo, hi = fcfg.band_bins
    frames = fcfg.n_frames(fcfg.block_samples(n))
    return {
        "minmax_sig_buckets": (
            lambda f, m, s: ops.minmax_sig_buckets(
                f, m, s, use_minmax=lcfg.use_minmax,
                n_buckets=scfg.index.n_buckets),
            ((n, d), jnp.bool_), ((d, lcfg.n_hash_fns), jnp.int32),
            ((lcfg.n_tables,), jnp.uint32)),
        "minmax_hash": (
            ops.minmax_hash,
            ((n, d), jnp.bool_), ((d, lcfg.n_hash_fns), jnp.int32)),
        "jaccard_popcount": (
            ops.jaccard_popcount,
            ((scfg.max_pairs_per_block, d // 32), jnp.uint32),
            ((scfg.max_pairs_per_block, d // 32), jnp.uint32)),
        "stft_mag": (
            ops.stft_mag,
            ((frames, fcfg.stft_len), jnp.float32),
            ((fcfg.stft_len,), jnp.float32),
            ((fcfg.stft_len, hi - lo), jnp.float32),
            ((fcfg.stft_len, hi - lo), jnp.float32)),
        "haar2d": (
            ops.haar2d,
            ((n, fcfg.img_freq, fcfg.img_time), jnp.float32)),
    }


@pytest.mark.parametrize("kernel", ["minmax_sig_buckets", "minmax_hash",
                                    "jaccard_popcount", "stft_mag", "haar2d"])
def test_kernel_compiles_for_v5e_at_paper_widths(compile_for_chip, kernel):
    fn, *shapes = _paper_cases()[kernel]
    compiled = compile_for_chip(fn, *shapes)
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{kernel} compiled without its Pallas kernel"


def test_guarded_step_keeps_tables_unpadded(compile_for_chip):
    """The step's insert scatters the (t, B, C) bucket tables in their own
    layout. A scatter through a flat B * C view needs C minor, which the
    TPU pads from 8 to 128 lanes: a station's 52 MB table would be copied
    into an 839 MB buffer and back, every step. Small tables do not
    show the layout choice, so this compiles the paper's shapes for one
    station, with the state donated as the pool step donates it."""
    cfg, scfg = config(), stream_config()
    lcfg, icfg = cfg.lsh, scfg.index
    n, words = scfg.block_fingerprints, cfg.fingerprint.fp_dim // 32
    icfg = dataclasses.replace(icfg, pk_words=words)
    state = jax.eval_shape(lambda: SI.init_index(lcfg, icfg))
    leaves, tree = jax.tree.flatten(state)

    def step(*args):
        st = jax.tree.unflatten(tree, args[:len(leaves)])
        sigs, buckets, ids, packed = args[len(leaves):]
        return SI.guarded_step(
            st, sigs, buckets, ids, None, lcfg, scfg.window_fingerprints,
            saturation=scfg.saturation_limit, occ_limit=scfg.occ_limit,
            packed=packed, max_pairs=scfg.max_pairs_per_block, verify=1)

    compiled = compile_for_chip(
        step, *[(x.shape, x.dtype) for x in leaves],
        ((n, lcfg.n_tables), jnp.uint32), ((n, lcfg.n_tables), jnp.int32),
        ((n,), jnp.int32), ((n, words), jnp.uint32),
        donate=tuple(range(len(leaves))))
    table = rf"[su]32\[((?:\d+,)*){lcfg.n_tables},{icfg.n_buckets}," \
            rf"{icfg.bucket_cap}\]\{{(\d+),"
    c_minor = sorted({m.group(0) for m in re.finditer(table,
                                                        compiled.as_text())
                      if int(m.group(2)) == m.group(1).count(",") + 2})
    assert not c_minor, f"bucket tables relaid out with C minor: {c_minor}"
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps < 64 * 2**20, f"{temps} B of temporaries"
