"""Pallas TPU kernel for Min-Max hash signature generation (paper §6.2).

The paper's CPU optimization is cache blocking: iterate fingerprint
*dimensions* outermost so rows of the hash-mapping table stay resident in
cache and are reused across the >60%-overlapping neighboring fingerprints.
The TPU translation is VMEM tiling: a (bn × bd) fingerprint tile and the
matching (bd × bh) hash-mapping tile are co-resident in VMEM and
min/max-accumulated over the D grid axis — dimensions are again the
reduction (outer) loop, hash-mapping rows are again the reused operand.

Grid: (N/bn, H/bh, D/bd) with D innermost (sequential reduction). Inside a
grid step the bd dimensions are a statically unrolled loop over (bn, bh)
planes: mapping row j broadcasts over sublanes and fingerprint column j
over lanes, so no (bn, bd, bh) intermediate is ever formed and fast-memory
use stays at a few tiles whatever the widths.

``minmax_sig_buckets`` extends the kernel with a fused epilogue: on the
last D step it folds the per-function min/max hashes into the per-table
signature and derives the salted bucket address in-register — the
signature fold + bucket addressing that would otherwise run as separate
jnp ops after the kernel returned. The jnp composition in
``core/lsh.signatures_and_buckets`` stays the bit-exact oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import hash_combine

BIG = np.int32(2**31 - 1)


def _minmax_update(fp_ref, map_ref, mn: jax.Array, mx: jax.Array
                   ) -> tuple[jax.Array, jax.Array]:
    """Fold a (bn, bd) int32 {0,1} fingerprint tile × (bd, bh) mapping
    tile into the running (bn, bh) min / max planes.

    Mapping values lie in [0, BIG], so the masked min is
    min_j max(m_j, 0 if set else BIG) and the masked max is
    max_j min(m_j, BIG if set else 0) — the oracle's ``where`` reductions
    as two elementwise ops per dimension.
    """
    on = fp_ref[...] != 0
    neg = jnp.where(on, jnp.int32(0), BIG)
    pos = jnp.where(on, BIG, jnp.int32(0))
    for j in range(fp_ref.shape[1]):
        row = map_ref[j:j + 1, :]
        mn = jnp.minimum(mn, jnp.maximum(row, neg[:, j:j + 1]))
        mx = jnp.maximum(mx, jnp.minimum(row, pos[:, j:j + 1]))
    return mn, mx


def _kernel(fp_ref, map_ref, min_ref, max_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        min_ref[...] = jnp.full(min_ref.shape, BIG, jnp.int32)
        max_ref[...] = jnp.zeros(max_ref.shape, jnp.int32)

    min_ref[...], max_ref[...] = _minmax_update(fp_ref, map_ref,
                                                min_ref[...], max_ref[...])


@functools.partial(jax.jit, static_argnames=("bn", "bd", "bh", "interpret"))
def minmax_hash(fp: jax.Array, mappings: jax.Array, *, bn: int = 32,
                bd: int = 128, bh: int = 256,
                interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """fp: (N, D) int32 {0,1}; mappings: (D, H) int32. Returns (N,H)x2
    int32.

    N % bn == 0, D % bd == 0, H % bh == 0 (ops.py pads as needed).
    """
    n, d = fp.shape
    d2, h = mappings.shape
    assert d == d2, (fp.shape, mappings.shape)
    assert n % bn == 0 and d % bd == 0 and h % bh == 0, (n, d, h, bn, bd, bh)
    out_shape = [
        jax.ShapeDtypeStruct((n, h), jnp.int32),
        jax.ShapeDtypeStruct((n, h), jnp.int32),
    ]
    mins, maxs = pl.pallas_call(
        _kernel,
        grid=(n // bn, h // bh, d // bd),
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((bd, bh), lambda i, j, k: (k, j)),
        ],
        out_specs=[
            pl.BlockSpec((bn, bh), lambda i, j, k: (i, j)),
            pl.BlockSpec((bn, bh), lambda i, j, k: (i, j)),
        ],
        out_shape=out_shape,
        interpret=interpret,
    )(fp.astype(jnp.int32), mappings)
    return mins, maxs


# ---------------------------------------------------------------------------
# fused signature fold + bucket addressing epilogue
# ---------------------------------------------------------------------------


def _sig_kernel(fp_ref, map_ref, salt_ref, sig_ref, bkt_ref, min_acc,
                max_acc, *, f: int, use_minmax: bool, n_buckets: int):
    kd = pl.program_id(2)

    @pl.when(kd == 0)
    def _init():
        min_acc[...] = jnp.full(min_acc.shape, BIG, jnp.int32)
        max_acc[...] = jnp.zeros(max_acc.shape, jnp.int32)

    min_acc[...], max_acc[...] = _minmax_update(fp_ref, map_ref,
                                                min_acc[...], max_acc[...])

    # Epilogue on the final reduction step: fold the f per-function hashes
    # of each table into its signature, then the salted bucket address —
    # still in VMEM, no HBM pass over the (N, H) min/max planes. The
    # mapping tile is function-major (column q*bt + j is function q of
    # table j), so each fold operand is a lane-aligned (bn, bt) slice.
    @pl.when(kd == pl.num_programs(2) - 1)
    def _fold():
        bt = sig_ref.shape[1]
        per_fn = min_acc[...].astype(jnp.uint32)
        if use_minmax:
            per_fn = hash_combine(per_fn, max_acc[...].astype(jnp.uint32))
        sig = jnp.zeros(sig_ref.shape, jnp.uint32)
        for q in range(f):               # static fold, matches fold_hashes
            sig = hash_combine(sig, per_fn[:, q * bt:(q + 1) * bt])
        sig_ref[...] = sig
        bkt = hash_combine(sig, salt_ref[...])
        bkt_ref[...] = (bkt & jnp.uint32(n_buckets - 1)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "f", "use_minmax", "n_buckets", "bn", "bd", "bt", "interpret"))
def minmax_sig_buckets(fp: jax.Array, mappings: jax.Array, salts: jax.Array,
                       *, f: int, use_minmax: bool, n_buckets: int,
                       bn: int = 32, bd: int = 128, bt: int = 128,
                       interpret: bool = False
                       ) -> tuple[jax.Array, jax.Array]:
    """fp (N, D) int32 {0,1} × mappings (D, T*f) → (signatures (N, T)
    uint32, bucket ids (N, T) int32).

    ``mappings`` is laid out in table tiles of ``bt`` tables, each tile
    function-major (``ops.minmax_sig_buckets`` permutes the func-fastest
    ``lsh.hash_mappings`` layout). ``salts`` is the (1, T) per-table
    bucket salt (``lsh.bucket_salts``). N % bn == 0, D % bd == 0,
    T % bt == 0, bt % 128 == 0.
    """
    n, d = fp.shape
    h = mappings.shape[1]
    t = h // f
    assert h == t * f and salts.shape == (1, t), (mappings.shape, salts.shape)
    assert n % bn == 0 and d % bd == 0 and t % bt == 0, (n, d, t, bn, bd, bt)
    bh = bt * f
    sig, bkt = pl.pallas_call(
        functools.partial(_sig_kernel, f=f, use_minmax=use_minmax,
                          n_buckets=n_buckets),
        grid=(n // bn, t // bt, d // bd),
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((bd, bh), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bt), lambda i, j, k: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bn, bt), lambda i, j, k: (i, j)),
            pl.BlockSpec((bn, bt), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, t), jnp.uint32),
            jax.ShapeDtypeStruct((n, t), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((bn, bh), jnp.int32),
                        pltpu.VMEM((bn, bh), jnp.int32)],
        interpret=interpret,
    )(fp.astype(jnp.int32), mappings, salts)
    return sig, bkt
