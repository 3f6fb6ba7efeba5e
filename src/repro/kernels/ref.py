"""Pure-jnp oracles for every Pallas kernel in this package.

Each function here defines the exact semantics the corresponding kernel in
``kernels/<name>.py`` must reproduce (tests assert allclose across shape /
dtype sweeps, with the kernel run in interpret mode on CPU).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# Precision of the fingerprint chain's f32 matmuls (STFT, band pooling,
# Haar — oracles and kernels alike). The TPU's default f32 matmul is one
# bf16 pass, which perturbs spectra enough for the top-K binarization to
# flip fingerprint bits against the CPU's f32 result; HIGHEST computes in
# f32 on every backend (a no-op on the CPU, so CPU results are unchanged).
MATMUL_PRECISION = jax.lax.Precision.HIGHEST

# ---------------------------------------------------------------------------
# Min-Max hash (paper §6.2, Algorithm 1) — the LSH hot spot
# ---------------------------------------------------------------------------


@jax.jit
def minmax_hash(fp: jax.Array, mappings: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Min and max of hash mappings over the non-zero dims of each fingerprint.

    Jitted so that the (N, D, H) masked broadcast is fused into the two
    reductions even when called eagerly (``lsh.search``): run op by op it
    would be materialised, tens of GB at the paper's widths.

    Args:
      fp: (N, D) boolean fingerprints.
      mappings: (D, H) int32 hash values in [0, 2**31) — one column per hash fn.

    Returns:
      (mins, maxs): each (N, H) int32. Rows with an all-zero fingerprint get
      mins = BIG, maxs = 0 (callers mask them out).
    """
    big = jnp.int32(np.int32(2**31 - 1))
    m = mappings[None, :, :]
    mask = fp[:, :, None]
    mins = jnp.where(mask, m, big).min(axis=1)
    maxs = jnp.where(mask, m, jnp.int32(0)).max(axis=1)
    return mins.astype(jnp.int32), maxs.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Standard-decomposition 2-D Haar wavelet transform (paper §5.1 step 2)
# ---------------------------------------------------------------------------


def haar_matrix(n: int) -> np.ndarray:
    """Full multilevel orthonormal 1-D Haar transform matrix (n x n), n=2^k.

    Row-ordering: [approximation, detail(level=log2(n)) ... detail(level=1)],
    i.e. the classic recursive construction: H_n = [[H_{n/2} ⊗ avg],
    [I_{n/2} ⊗ diff]].
    """
    assert n & (n - 1) == 0, f"haar size {n} must be a power of two"
    h = np.array([[1.0]])
    while h.shape[0] < n:
        m = h.shape[0]
        top = np.kron(h, np.array([[1.0, 1.0]]) / math.sqrt(2.0))
        bot = np.kron(np.eye(m), np.array([[1.0, -1.0]]) / math.sqrt(2.0))
        h = np.concatenate([top, bot], axis=0)
    return h.astype(np.float32)


def haar2d(imgs: jax.Array) -> jax.Array:
    """Standard-decomposition 2-D Haar transform of (..., H, W) images.

    The standard (tensor-product) decomposition is two dense orthogonal
    matmuls — the MXU-native formulation (DESIGN.md §3.4).
    """
    h, w = imgs.shape[-2:]
    th = jnp.asarray(haar_matrix(h), imgs.dtype)
    tw = jnp.asarray(haar_matrix(w), imgs.dtype)
    return jnp.einsum("ij,...jk,lk->...il", th, imgs, tw,
                      precision=MATMUL_PRECISION)


# ---------------------------------------------------------------------------
# STFT magnitude via DFT matmul (paper §5.1 step 1)
# ---------------------------------------------------------------------------


def dft_matrices(frame_len: int, n_freq: int) -> tuple[np.ndarray, np.ndarray]:
    """Real/imag DFT analysis matrices (frame_len, n_freq) for rfft bins."""
    t = np.arange(frame_len)[:, None]
    k = np.arange(n_freq)[None, :]
    ang = -2.0 * np.pi * t * k / frame_len
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def stft_mag(frames: jax.Array, window: jax.Array, dft_r: jax.Array,
             dft_i: jax.Array) -> jax.Array:
    """Power spectrogram of pre-framed data.

    frames: (N, L); window: (L,); dft_r/dft_i: (L, K). Returns (N, K) power.
    """
    xw = frames * window[None, :]
    re = jnp.matmul(xw, dft_r, precision=MATMUL_PRECISION)
    im = jnp.matmul(xw, dft_i, precision=MATMUL_PRECISION)
    return re * re + im * im


# ---------------------------------------------------------------------------
# Packed-bit Jaccard similarity (candidate verification)
# ---------------------------------------------------------------------------


def jaccard_popcount(a: jax.Array, b: jax.Array) -> jax.Array:
    """Jaccard similarity of row-aligned packed binary vectors.

    a, b: (P, W) uint32 packed fingerprints. Returns (P,) float32; empty
    unions give 0.
    """
    inter = jax.lax.population_count(a & b).astype(jnp.int32).sum(axis=-1)
    union = jax.lax.population_count(a | b).astype(jnp.int32).sum(axis=-1)
    return jaccard_from_counts(inter, union)


def jaccard_from_counts(inter: jax.Array, union: jax.Array) -> jax.Array:
    """Intersection / union popcounts → Jaccard (float32; empty unions
    give 0). Shared by the oracle and the Pallas wrapper so both divide
    with the same lowering."""
    return jnp.where(union > 0, inter / jnp.maximum(union, 1), 0.0).astype(
        jnp.float32
    )


# ---------------------------------------------------------------------------
# Flash attention (serving/training hot spot; GQA + causal)
# ---------------------------------------------------------------------------


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True) -> jax.Array:
    """Reference attention. q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D).

    Hq % Hkv == 0 (GQA). Softmax in fp32. Returns (B, Hq, Sq, D) in q.dtype.
    """
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = hq // hkv
    kx = jnp.repeat(k, group, axis=1)
    vx = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        kx.astype(jnp.float32)) / math.sqrt(d)
    if causal:
        qi = jnp.arange(sq)[:, None] + (sk - sq)
        ki = jnp.arange(sk)[None, :]
        scores = jnp.where(ki <= qi, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", w, vx.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Fused Mamba1 selective scan (falcon-mamba memory-wall fix)
# ---------------------------------------------------------------------------


def mamba_scan(xdt: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
               c: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Sequential-reference selective scan.

    xdt/dt: (B, S, Di); a: (Di, N); b/c: (B, S, N) →
    (y (B, S, Di), h_final (B, Di, N)).
    """
    bsz, s, di = xdt.shape
    n = a.shape[1]

    def step(h, t):
        g = jnp.exp(dt[:, t, :, None] * a[None])          # (B, Di, N)
        h = g * h + xdt[:, t, :, None] * b[:, t, None, :]
        y = jnp.einsum("bdn,bn->bd", h, c[:, t])
        return h, y

    h0 = jnp.zeros((bsz, di, n), jnp.float32)
    h_final, ys = jax.lax.scan(step, h0, jnp.arange(s))
    return jnp.swapaxes(ys, 0, 1).astype(xdt.dtype), h_final
