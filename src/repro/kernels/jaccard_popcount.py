"""Pallas TPU kernel: packed-bit Jaccard counts for candidate pairs.

Fingerprints are packed 32 bits/lane; the kernel evaluates
popcount(a&b) and popcount(a|b) per row on the VPU. Used to exactly
verify LSH candidate pairs (an exactness knob the paper's hash-match-count
proxy lacks).

The kernel returns the integer counts, not the ratio: the division runs
outside as the same jnp expression the oracle uses
(``ref.jaccard_from_counts``), so kernel and oracle agree bit for bit
whatever the backend's division lowering. The per-row sums are written
lane-dense — a (2, bp) block of a (2, P) output, pairs along lanes — by
folding the word axis down to one 128-lane tile and transposing it
in-kernel; a 1-D (bp,) output block does not match XLA's layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _lane_sums(x: jax.Array) -> jax.Array:
    """(bp, w) int32 → (1, bp) per-row sums laid out along lanes."""
    w = x.shape[1]
    while w % 256 == 0:              # lane-aligned halving down to 128
        w //= 2
        x = x[:, :w] + x[:, w:]
    return jnp.sum(x.T, axis=0, keepdims=True)


def _kernel(a_ref, b_ref, out_ref):
    a = a_ref[...]
    b = b_ref[...]
    inter = jax.lax.population_count(a & b).astype(jnp.int32)
    union = jax.lax.population_count(a | b).astype(jnp.int32)
    out_ref[0:1, :] = _lane_sums(inter)
    out_ref[1:2, :] = _lane_sums(union)


@functools.partial(jax.jit, static_argnames=("bp", "interpret"))
def jaccard_counts(a: jax.Array, b: jax.Array, *, bp: int = 512,
                   interpret: bool = False) -> jax.Array:
    """a, b: (P, W) uint32 packed rows → (2, P) int32: row 0 the
    intersection popcounts, row 1 the union popcounts. P % bp == 0 and
    bp % 128 == 0 (ops.py pads)."""
    p, w = a.shape
    assert a.shape == b.shape and p % bp == 0 and bp % 128 == 0, \
        (a.shape, b.shape, bp)
    return pl.pallas_call(
        _kernel,
        grid=(p // bp,),
        in_specs=[
            pl.BlockSpec((bp, w), lambda i: (i, 0)),
            pl.BlockSpec((bp, w), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((2, bp), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((2, p), jnp.int32),
        interpret=interpret,
    )(a, b)
