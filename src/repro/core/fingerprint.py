"""Fingerprint extraction (paper §5): waveform → binary fingerprints.

Chain (Figure 3): spectrogram → banded spectral images → 2-D Haar wavelet →
median/MAD normalization (sampled, §5.2) → top-K most anomalous coefficients
→ sign binarization (2 bits per coefficient).

The bandpass filter is applied *inside* the fingerprinter by cutting the
spectrogram at the band corners (the paper's §6.5 extension), plus an
optional time-domain windowed-sinc bandpass for the raw trace.

All steps are jit-friendly with static shapes; the heavy steps dispatch to
Pallas kernels (``use_pallas=True``) or their jnp oracles.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.ref import MATMUL_PRECISION, dft_matrices
from repro.utils import pack_bits


@dataclasses.dataclass(frozen=True)
class FingerprintConfig:
    """Defaults give the paper's 8192-dim fingerprints at 100 Hz."""

    fs: float = 100.0
    # STFT
    stft_len: int = 200          # 2 s analysis window
    stft_hop: int = 25           # 0.25 s hop
    # bandpass (paper evaluation: 3–20 Hz on the NZ dataset)
    band_lo_hz: float = 3.0
    band_hi_hz: float = 20.0
    time_domain_bandpass: bool = False   # optional windowed-sinc prefilter
    bp_taps: int = 255
    # spectral images
    img_freq: int = 32           # freq bins after pooling (power of two)
    img_time: int = 128          # spectrogram frames per image (power of two)
    img_hop: int = 8             # frames between fingerprints (2 s lag)
    # fingerprint
    top_k: int = 400             # most anomalous wavelet coefficients kept
    mad_sample_rate: float = 0.1  # §5.2 MAD-via-sampling
    use_pallas: bool = False

    @property
    def n_rfft(self) -> int:
        return self.stft_len // 2 + 1

    @property
    def band_bins(self) -> tuple[int, int]:
        """[lo, hi) rfft bin range kept by the band filter."""
        lo = int(math.ceil(self.band_lo_hz * self.stft_len / self.fs))
        hi = int(math.floor(self.band_hi_hz * self.stft_len / self.fs)) + 1
        lo = max(0, min(lo, self.n_rfft - 1))
        hi = max(lo + 1, min(hi, self.n_rfft))
        return lo, hi

    @property
    def n_coeff(self) -> int:
        return self.img_freq * self.img_time

    @property
    def fp_dim(self) -> int:
        return 2 * self.n_coeff  # sign encoding: 2 bits / coefficient

    @property
    def window_samples(self) -> int:
        """Raw samples spanned by one fingerprint."""
        return (self.img_time - 1) * self.stft_hop + self.stft_len

    @property
    def lag_samples(self) -> int:
        return self.img_hop * self.stft_hop

    def n_fingerprints(self, n_samples: int) -> int:
        nf = self.n_frames(n_samples)
        return max(0, (nf - self.img_time) // self.img_hop + 1)

    def n_frames(self, n_samples: int) -> int:
        return max(0, (n_samples - self.stft_len) // self.stft_hop + 1)

    @property
    def overlap_fingerprints(self) -> int:
        """Adjacent fingerprints sharing samples (self-match exclusion)."""
        return self.img_time // self.img_hop

    @property
    def halo_samples(self) -> int:
        """Samples a chunk boundary must overlap so that fingerprints are
        sample-exact across a chunked/streaming split (window minus lag)."""
        return self.window_samples - self.lag_samples

    def block_samples(self, n_fingerprints: int) -> int:
        """Samples spanned by a block of ``n_fingerprints`` consecutive
        fingerprints (the streaming ingest unit)."""
        return (n_fingerprints - 1) * self.lag_samples + self.window_samples


# ---------------------------------------------------------------------------
# framing + optional time-domain bandpass
# ---------------------------------------------------------------------------


def frame(x: jax.Array, frame_len: int, hop: int) -> jax.Array:
    """(T,) → (n_frames, frame_len) strided framing via gather."""
    n = max(0, (x.shape[-1] - frame_len) // hop + 1)
    idx = jnp.arange(n)[:, None] * hop + jnp.arange(frame_len)[None, :]
    return x[idx]


def bandpass_kernel(cfg: FingerprintConfig) -> np.ndarray:
    """Windowed-sinc FIR bandpass taps (no scipy dependency)."""
    nt = cfg.bp_taps
    t = np.arange(nt) - (nt - 1) / 2.0
    def lp(fc):
        h = np.sinc(2 * fc / cfg.fs * t) * (2 * fc / cfg.fs)
        return h * np.hamming(nt)
    h = lp(cfg.band_hi_hz) - lp(cfg.band_lo_hz)
    return h.astype(np.float32)


def bandpass(x: jax.Array, cfg: FingerprintConfig) -> jax.Array:
    taps = jnp.asarray(bandpass_kernel(cfg))
    return jnp.convolve(x, taps, mode="same", precision=MATMUL_PRECISION)


# ---------------------------------------------------------------------------
# spectrogram + spectral images
# ---------------------------------------------------------------------------


def _pool_matrix(n_in: int, n_out: int) -> np.ndarray:
    """Average-pooling matrix (n_in, n_out) with near-equal bin spans."""
    edges = np.linspace(0, n_in, n_out + 1)
    m = np.zeros((n_in, n_out), np.float32)
    for j in range(n_out):
        lo, hi = edges[j], edges[j + 1]
        idx = np.arange(int(np.floor(lo)), int(np.ceil(hi)))
        for i in idx:
            w = min(hi, i + 1) - max(lo, i)
            if w > 0:
                m[i, j] = w
    m /= m.sum(axis=0, keepdims=True)
    return m


def spectrogram(x: jax.Array, cfg: FingerprintConfig) -> jax.Array:
    """(T,) waveform → (n_frames, banded_bins) power spectrogram."""
    if cfg.time_domain_bandpass:
        x = bandpass(x, cfg)
    frames = frame(x, cfg.stft_len, cfg.stft_hop)
    lo, hi = cfg.band_bins
    dr, di = dft_matrices(cfg.stft_len, cfg.n_rfft)
    window = jnp.asarray(np.hanning(cfg.stft_len).astype(np.float32))
    # Band cut at the fingerprinter (paper §6.5): only [lo, hi) columns.
    spec = ops.stft_mag(frames, window, jnp.asarray(dr[:, lo:hi]),
                        jnp.asarray(di[:, lo:hi]), use_pallas=cfg.use_pallas)
    return spec


def spectral_images(spec: jax.Array, cfg: FingerprintConfig) -> jax.Array:
    """(n_frames, B) spectrogram → (n_images, img_freq, img_time)."""
    n_frames, b = spec.shape
    pool = jnp.asarray(_pool_matrix(b, cfg.img_freq))
    pooled = jnp.matmul(spec, pool, precision=MATMUL_PRECISION)
    n_img = (n_frames - cfg.img_time) // cfg.img_hop + 1
    idx = (jnp.arange(n_img)[:, None] * cfg.img_hop
           + jnp.arange(cfg.img_time)[None, :])
    imgs = pooled[idx]  # (n_img, img_time, img_freq)
    return jnp.swapaxes(imgs, 1, 2)  # (n_img, img_freq, img_time)


# ---------------------------------------------------------------------------
# wavelet + MAD normalization (§5.2) + top-K binarization
# ---------------------------------------------------------------------------


def wavelet_coeffs(imgs: jax.Array, cfg: FingerprintConfig) -> jax.Array:
    """(N, F, T) → (N, F*T) Haar coefficients."""
    coeffs = ops.haar2d(imgs, use_pallas=cfg.use_pallas)
    return coeffs.reshape(imgs.shape[0], -1)


def mad_stats(coeffs: jax.Array, sample_rate: float,
              key: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Median + MAD per coefficient, estimated from a row sample (§5.2).

    sample_rate == 1.0 reproduces the exact two-pass statistics.
    """
    n = coeffs.shape[0]
    if sample_rate >= 1.0:
        sample = coeffs
    else:
        m = max(2, int(round(n * sample_rate)))
        rows = jax.random.choice(key, n, shape=(m,), replace=False)
        sample = coeffs[rows]
    med = jnp.median(sample, axis=0)
    mad = jnp.median(jnp.abs(sample - med[None, :]), axis=0)
    return med, mad


def mad_normalize(coeffs: jax.Array, med: jax.Array,
                  mad: jax.Array) -> jax.Array:
    return (coeffs - med[None, :]) / (mad[None, :] + 1e-9)


def topk_binarize(z: jax.Array, cfg: FingerprintConfig) -> jax.Array:
    """Keep top-K |z| per row; encode signs as 2 bits (paper step 4-5).

    Returns bool (N, 2*C): even positions = (coeff in top-K and > 0),
    odd positions = (coeff in top-K and < 0).
    """
    a = jnp.abs(z)
    kth = jax.lax.top_k(a, cfg.top_k)[0][:, -1]  # (N,)
    mask = a >= kth[:, None]
    pos = mask & (z > 0)
    neg = mask & (z < 0)
    inter = jnp.stack([pos, neg], axis=-1)  # (N, C, 2)
    return inter.reshape(z.shape[0], -1)


# ---------------------------------------------------------------------------
# end-to-end
# ---------------------------------------------------------------------------


def coeffs_from_waveform(x: jax.Array, cfg: FingerprintConfig) -> jax.Array:
    """Waveform (T,) → raw Haar coefficients (N, n_coeff).

    The normalization-free front half of the pipeline; streaming ingest
    calls this per block to feed its running median/MAD estimator before
    binarization (the §5.2 two-pass structure made incremental).
    """
    spec = spectrogram(x, cfg)
    imgs = spectral_images(spec, cfg)
    return wavelet_coeffs(imgs, cfg)


def binarize_coeffs(coeffs: jax.Array, cfg: FingerprintConfig,
                    med_mad: tuple[jax.Array, jax.Array]
                    ) -> tuple[jax.Array, jax.Array]:
    """(N, n_coeff) coefficients + (med, mad) → (bits, packed) fingerprints."""
    z = mad_normalize(coeffs, *med_mad)
    bits = topk_binarize(z, cfg)
    return bits, pack_bits(bits)


def fingerprints_from_waveform(
    x: jax.Array, cfg: FingerprintConfig, *, key: jax.Array | None = None,
    med_mad: tuple[jax.Array, jax.Array] | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Waveform (T,) → (fingerprints bool (N, fp_dim), packed uint32).

    If ``med_mad`` is given, those statistics are used (the paper's two-pass
    structure: stats once, then partition-parallel normalization).
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    coeffs = coeffs_from_waveform(x, cfg)
    if med_mad is None:
        med_mad = mad_stats(coeffs, cfg.mad_sample_rate, key)
    return binarize_coeffs(coeffs, cfg, med_mad)
