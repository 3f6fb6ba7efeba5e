"""Pallas TPU kernel: fused windowing + DFT matmul + power magnitude.

FFT butterflies map poorly onto the 128×128 systolic MXU; for the short,
fixed analysis windows used by the fingerprinter the STFT is a dense
(frames @ DFT) matmul (DESIGN.md §3.3). The kernel fuses the Hann window,
both real/imag matmuls and |·|² so only the power spectrogram hits HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import MATMUL_PRECISION


def _kernel(fr_ref, win_ref, dr_ref, di_ref, out_ref):
    x = fr_ref[...] * win_ref[...]  # (bf, L) * (1, L)
    re = jax.lax.dot_general(x, dr_ref[...], (((1,), (0,)), ((), ())),
                             precision=MATMUL_PRECISION,
                             preferred_element_type=jnp.float32)
    im = jax.lax.dot_general(x, di_ref[...], (((1,), (0,)), ((), ())),
                             precision=MATMUL_PRECISION,
                             preferred_element_type=jnp.float32)
    out_ref[...] = (re * re + im * im).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bf", "interpret"))
def stft_mag(frames: jax.Array, window: jax.Array, dft_r: jax.Array,
             dft_i: jax.Array, *, bf: int = 256,
             interpret: bool = False) -> jax.Array:
    """frames: (N, L); window: (1, L); dft_r/i: (L, K). N % bf == 0."""
    n, l = frames.shape
    k = dft_r.shape[1]
    assert n % bf == 0, (n, bf)
    grid = (n // bf,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bf, l), lambda i: (i, 0)),
            pl.BlockSpec((1, l), lambda i: (0, 0)),
            pl.BlockSpec((l, k), lambda i: (0, 0)),
            pl.BlockSpec((l, k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bf, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        interpret=interpret,
    )(frames, window, dft_r, dft_i)
