"""Jit'd public wrappers for the Pallas kernels.

Each wrapper:
  * pads inputs to kernel-friendly block multiples and un-pads outputs,
  * compiles the kernel on TPU and runs it in interpret mode on the CPU
    (kernels VALIDATE on CPU via interpret=True; TPU is the compile
    target); any other platform is an error, never a silent interpreter,
  * falls back to the pure-jnp oracle when ``use_pallas=False`` (the default
    for distributed dry-run lowering, where XLA-partitionable HLO is wanted).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as _ref
from repro.kernels import flash_attention as _fa
from repro.kernels import mamba_scan as _ms
from repro.kernels import haar2d as _haar
from repro.kernels import jaccard_popcount as _jac
from repro.kernels import minmax_hash as _mm
from repro.kernels import stft_mag as _stft
from repro.utils import round_up


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise NotImplementedError(
            f"Pallas kernels compile for tpu and interpret on cpu; "
            f"no path for platform {backend!r} (use_pallas=False runs the "
            f"jnp oracles)")
    return backend == "cpu"


def _pad_axis(x: jax.Array, axis: int, mult: int, value=0) -> jax.Array:
    n = x.shape[axis]
    pad = round_up(max(n, 1), mult) - n
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------


def minmax_hash(fp: jax.Array, mappings: jax.Array, *, use_pallas: bool = True,
                bn: int = 32, bd: int = 128, bh: int = 256):
    """(N, D) fingerprints × (D, H) mappings -> (mins, maxs), each (N, H)."""
    if not use_pallas:
        return _ref.minmax_hash(fp.astype(bool), mappings)
    n, d = fp.shape
    h = mappings.shape[1]
    bn = min(bn, round_up(n, 8))
    bd = min(bd, round_up(d, 128))
    bh = min(bh, round_up(h, 128))
    fp_p = _pad_axis(_pad_axis(fp.astype(jnp.int32), 0, bn), 1, bd)
    mp_p = _pad_axis(_pad_axis(mappings, 0, bd), 1, bh, value=0)
    mins, maxs = _mm.minmax_hash(fp_p, mp_p, bn=bn, bd=bd, bh=bh,
                                 interpret=_interpret())
    return mins[:n, :h], maxs[:n, :h]


def minmax_sig_buckets(fp: jax.Array, mappings: jax.Array, salts: jax.Array,
                       *, use_minmax: bool, n_buckets: int, bn: int = 32,
                       bd: int = 128, bt: int = 128):
    """(N, D) fingerprints × (D, T*f) mappings → per-table (signatures,
    bucket ids), each (N, T) — the Min-Max kernel with the signature fold
    + bucket addressing fused into its epilogue.

    Pallas-only entry: the bit-exact jnp oracle lives in
    ``core/lsh.signatures_and_buckets`` (which is also the only caller
    that decides between the two).
    """
    n, d = fp.shape
    t = salts.shape[0]
    f = mappings.shape[1] // t
    bn = min(bn, round_up(n, 8))
    bd = min(bd, round_up(d, 128))
    tp = round_up(t, bt)
    fp_p = _pad_axis(_pad_axis(fp.astype(jnp.int32), 0, bn), 1, bd)
    # func-fastest (D, T*f) → pad to whole table tiles → function-major
    # inside each tile of bt tables (the kernel's fold slices); the pad
    # tables are dropped again by the final [:t] slice
    mp = _pad_axis(mappings.reshape(d, t, f), 1, tp)
    mp = mp.reshape(d, tp // bt, bt, f).transpose(0, 1, 3, 2)
    mp_p = _pad_axis(mp.reshape(d, tp * f), 0, bd)
    salt_p = _pad_axis(salts.reshape(1, -1).astype(jnp.uint32), 1, bt)
    sig, bkt = _mm.minmax_sig_buckets(
        fp_p, mp_p, salt_p, f=f, use_minmax=use_minmax, n_buckets=n_buckets,
        bn=bn, bd=bd, bt=bt, interpret=_interpret())
    return sig[:n, :t], bkt[:n, :t]


def haar2d(imgs: jax.Array, *, use_pallas: bool = True, bn: int = 32):
    """Standard-decomposition 2-D Haar transform of (N, H, W) images."""
    if not use_pallas:
        return _ref.haar2d(imgs)
    n, h, w = imgs.shape
    th = jnp.asarray(_ref.haar_matrix(h), imgs.dtype)
    tw = jnp.asarray(_ref.haar_matrix(w), imgs.dtype)
    bn = min(bn, round_up(n, 8))
    imgs_p = _pad_axis(imgs, 0, bn)
    out = _haar.haar2d(imgs_p, th, tw, bn=bn, interpret=_interpret())
    return out[:n]


def stft_mag(frames: jax.Array, window: jax.Array, dft_r: jax.Array,
             dft_i: jax.Array, *, use_pallas: bool = True, bf: int = 256):
    """(N, L) frames -> (N, K) power spectrogram."""
    if not use_pallas:
        return _ref.stft_mag(frames, window, dft_r, dft_i)
    n, l = frames.shape
    k = dft_r.shape[1]
    bf = min(bf, round_up(n, 8))
    lp = round_up(l, 128)
    kp = round_up(k, 128)
    frames_p = _pad_axis(_pad_axis(frames, 0, bf), 1, lp)
    win_p = _pad_axis(window.reshape(1, -1), 1, lp)
    dr_p = _pad_axis(_pad_axis(dft_r, 0, lp), 1, kp)
    di_p = _pad_axis(_pad_axis(dft_i, 0, lp), 1, kp)
    out = _stft.stft_mag(frames_p, win_p, dr_p, di_p, bf=bf,
                         interpret=_interpret())
    return out[:n, :k]


def jaccard_popcount(a: jax.Array, b: jax.Array, *, use_pallas: bool = True,
                     bp: int = 512):
    """Row-wise Jaccard of packed (P, W) uint32 fingerprints -> (P,) f32."""
    if not use_pallas:
        return _ref.jaccard_popcount(a, b)
    p, w = a.shape
    bp = min(bp, round_up(p, 128))
    counts = _jac.jaccard_counts(_pad_axis(a, 0, bp), _pad_axis(b, 0, bp),
                                 bp=bp, interpret=_interpret())
    return _ref.jaccard_from_counts(counts[0, :p], counts[1, :p])


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, use_pallas: bool = True,
                    bq: int = 128, bk: int = 128):
    """GQA flash attention; q (B,Hq,Sq,D), k/v (B,Hkv,Sk,D) -> (B,Hq,Sq,D)."""
    if not use_pallas:
        return _ref.flash_attention(q, k, v, causal=causal)
    b, hq, sq, d = q.shape
    sk = k.shape[2]
    bq_ = min(bq, round_up(sq, 8))
    bk_ = min(bk, round_up(sk, 8))
    sq_p = round_up(sq, bq_)
    sk_p = round_up(sk, bk_)
    q_p = _pad_axis(q, 2, bq_)
    # Pad keys at the FRONT would shift causal offsets; pad at the back and
    # mask padded keys via an explicit -inf trick: padded k rows are zeros,
    # which under causal masking with offset sk-sq are attended — so instead
    # pad queries/keys and rely on the kernel's causal mask computed with the
    # ORIGINAL sq/sk. Simplest correct path: require multiples or fall back.
    if sq_p != sq or sk_p != sk:
        return _ref.flash_attention(q, k, v, causal=causal)
    del q_p
    return _fa.flash_attention(q, k, v, causal=causal, bq=bq_, bk=bk_,
                               interpret=_interpret())


def mamba_scan(xdt, dt, a, b, c, *, use_pallas: bool = True, bd: int = 128):
    """Fused selective scan; (B,S,Di)×(Di,N) → (y, h_final)."""
    if not use_pallas:
        return _ref.mamba_scan(xdt, dt, a, b, c)
    di = xdt.shape[2]
    bd = min(bd, di)
    while di % bd:
        bd //= 2
    return _ms.mamba_scan(xdt, dt, a, b, c, bd=max(bd, 1),
                          interpret=_interpret())
