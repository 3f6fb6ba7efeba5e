"""Pallas TPU kernel: fused standard-decomposition 2-D Haar transform.

The multilevel 1-D Haar transform is a fixed orthogonal matrix (≤128×128
here), so the standard 2-D decomposition is two dense matmuls — an exact
MXU fit. The kernel fuses both matmuls per image block so intermediate
coefficients never round-trip to HBM (DESIGN.md §3.4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import MATMUL_PRECISION


def _kernel(img_ref, th_ref, tw_ref, out_ref):
    x = img_ref[...]  # (bn, H, W)
    th = th_ref[...]  # (H, H)
    tw = tw_ref[...]  # (W, W)
    # rows: y[n, h, v] = sum_w x[n, h, w] * tw[v, w]
    y = jax.lax.dot_general(
        x, tw, (((2,), (1,)), ((), ())), precision=MATMUL_PRECISION,
        preferred_element_type=jnp.float32)  # (bn, H, V)
    # cols: z[n, u, v] = sum_h th[u, h] * y[n, h, v]
    z = jax.lax.dot_general(
        y, th, (((1,), (1,)), ((), ())), precision=MATMUL_PRECISION,
        preferred_element_type=jnp.float32)  # (bn, V, U) -> transpose
    out_ref[...] = jnp.swapaxes(z, 1, 2).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def haar2d(imgs: jax.Array, th: jax.Array, tw: jax.Array, *, bn: int = 32,
           interpret: bool = False) -> jax.Array:
    """imgs: (N, H, W) float; th: (H, H); tw: (W, W). N % bn == 0."""
    n, h, w = imgs.shape
    assert n % bn == 0, (n, bn)
    grid = (n // bn,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, h, w), lambda i: (i, 0, 0)),
            pl.BlockSpec((h, h), lambda i: (0, 0)),
            pl.BlockSpec((w, w), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, h, w), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h, w), imgs.dtype),
        interpret=interpret,
    )(imgs, th, tw)
