"""Shared utilities: integer mixing, bit packing, segment helpers.

TPU-friendly primitives used across the FAST pipeline. The paper uses
murmurhash for MinHash permutations; we use a splitmix-style mixer that
vectorizes over int32 lanes (DESIGN.md §3.8).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Integer hashing (splitmix32-style finalizer, vector-lane friendly)
# ---------------------------------------------------------------------------

_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)
_GOLDEN = np.uint32(0x9E3779B9)


def mix32(x: jax.Array) -> jax.Array:
    """Avalanche mixer over uint32 lanes (murmur3 finalizer)."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 13)
    x = x * _M2
    x = x ^ (x >> 16)
    return x


def hash_u32(x: jax.Array, seed) -> jax.Array:
    """Seeded uint32 hash of integer input (any int dtype)."""
    seed = jnp.asarray(seed, jnp.uint32)
    return mix32(x.astype(jnp.uint32) + seed * _GOLDEN)


def hash_combine(a: jax.Array, b: jax.Array) -> jax.Array:
    """Order-sensitive combine of two uint32 hash streams (boost-style)."""
    a = a.astype(jnp.uint32)
    b = b.astype(jnp.uint32)
    return a ^ (b + _GOLDEN + (a << 6) + (a >> 2))


def fold_hashes(h: jax.Array, axis: int = -1) -> jax.Array:
    """Reduce an axis of uint32 hashes into one uint32 via hash_combine."""
    h = jnp.moveaxis(h, axis, 0)

    def body(carry, x):
        return hash_combine(carry, x), None

    # zeros_like keeps the input's type, so inside a shard_map region the
    # carry is varying over the same mesh axes as ``h`` (check_vma)
    init = jnp.zeros_like(h[0], jnp.uint32)
    out, _ = jax.lax.scan(body, init, h)
    return out


# ---------------------------------------------------------------------------
# Bit packing for binary fingerprints
# ---------------------------------------------------------------------------


def pack_bits(bits: jax.Array) -> jax.Array:
    """Pack a boolean array (..., d) with d % 32 == 0 into uint32 (..., d//32).

    Bit j of word w corresponds to input position w * 32 + j.
    """
    d = bits.shape[-1]
    assert d % 32 == 0, f"fingerprint dim {d} not a multiple of 32"
    b = bits.astype(jnp.uint32).reshape(*bits.shape[:-1], d // 32, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return (b << shifts).sum(axis=-1).astype(jnp.uint32)


def unpack_bits(words: jax.Array, d: int) -> jax.Array:
    """Inverse of pack_bits; returns bool (..., d)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    b = (words[..., None] >> shifts) & jnp.uint32(1)
    return b.reshape(*words.shape[:-1], words.shape[-1] * 32)[..., :d].astype(bool)


def popcount(x: jax.Array) -> jax.Array:
    """Per-lane popcount of uint32 words."""
    return jax.lax.population_count(x.astype(jnp.uint32)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Segment helpers on sorted keys (the TPU group-by substrate, DESIGN.md §3.1)
# ---------------------------------------------------------------------------


def segment_starts(sorted_keys: jax.Array) -> jax.Array:
    """Boolean mask: True where a run of equal keys begins (keys sorted)."""
    first = jnp.ones((1,) + sorted_keys.shape[1:], bool)
    return jnp.concatenate([first, sorted_keys[1:] != sorted_keys[:-1]], axis=0)


def segment_ids_from_starts(starts: jax.Array) -> jax.Array:
    """Integer segment id per element (cumsum of run starts, 0-based)."""
    return jnp.cumsum(starts.astype(jnp.int32)) - 1


def run_lengths(sorted_keys: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(segment_ids, length_of_that_segment_per_element) for sorted keys."""
    starts = segment_starts(sorted_keys)
    seg = segment_ids_from_starts(starts)
    ones = jnp.ones_like(seg)
    counts = jax.ops.segment_sum(ones, seg, num_segments=sorted_keys.shape[0])
    return seg, counts[seg]


def rank_in_run(sorted_keys: jax.Array) -> jax.Array:
    """0-based rank of each element inside its run of equal (sorted) keys."""
    starts = segment_starts(sorted_keys)
    idx = jnp.arange(sorted_keys.shape[0], dtype=jnp.int32)
    start_idx = jnp.where(starts, idx, 0)
    run_start = jax.lax.associative_scan(jnp.maximum, start_idx)
    return idx - run_start


# ---------------------------------------------------------------------------
# Misc
# ---------------------------------------------------------------------------


def tree_bytes(tree) -> int:
    """Total byte size of a pytree of arrays / ShapeDtypeStructs."""
    return sum(
        int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
        for x in jax.tree.leaves(tree)
    )


def tree_param_count(tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b
