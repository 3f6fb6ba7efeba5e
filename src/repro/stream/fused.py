"""Single-dispatch streaming hot path: fused chunk step + station pool.

The PR-1/2 hot path ran each per-block stage as its own jitted call —
``block_coeffs`` (STFT → band cut → Haar), then ``stream_step`` (binarize →
sign → expire → insert → query) — with the ring advance and all staging on
the host in between. Here the whole chain is **one** ``jax.jit`` entry with
``donate_argnums`` on the full device state:

  ``FusedState`` = index tables + ring halo + frozen MAD statistics.

``step_advance`` is the steady-state entry: its input is only the *new*
samples of the next block (``block_fingerprints * lag_samples`` of them);
the overlapping head — the STFT halo — is the ``halo`` buffer retained on
device from the previous step, so the WaveformRing advance is part of the
traced program, not a host copy. ``step_block`` is the re-seeding entry
(first block after a freeze, restore, or masked flush tail): it takes a
whole framed block plus a fingerprint-valid mask and leaves the halo
primed for subsequent advance steps.

Because every buffer of ``FusedState`` is donated, chunk N+1 writes into
chunk N's memory: steady state runs with zero per-chunk HBM allocation and
exactly one dispatch (the retracing/donation guards in
``tests/test_stream.py`` pin both properties).

``pool_step_advance`` / ``pool_step_block`` are the same two entries with
every state leaf carrying a leading station axis, stepped via ``vmap``:
one executable serves S stations (the ISSUE-3 index pool) instead of S
sequential single-station engines each paying their own dispatch. When a
fingerprint-sharded mesh is available the pool axis is the natural
candidate for ``shard_map``; on a single device the vmap alone already
amortizes dispatch + pipeline overheads across stations.

``pool_step_block`` is also the **batch** entry (ISSUE 5, one core two
drivers): ``core.detect.detect_events`` replays archive traces through
it block by block — whole framed blocks with a tail mask, no ring state
needed — so offline reprocessing and the live service run the identical
guarded program.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import dist
from repro.core import fingerprint as fp_mod
from repro.core import lsh as lsh_mod
from repro.core.fingerprint import FingerprintConfig
from repro.core.lsh import LSHConfig, Pairs
from repro.stream import index as index_mod
from repro.stream.index import IndexState


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FusedState:
    """Everything the fused step owns on device (all donated).

    Solo form: ``index`` (t, B, C), ``halo`` (halo_samples,), ``med``/
    ``mad`` (n_coeff,). Pool form: the same leaves with a leading (S,)
    station axis (see ``init_pool_state``).
    """

    index: IndexState
    halo: jax.Array
    med: jax.Array
    mad: jax.Array


def init_state(index: IndexState, halo_samples: int, med, mad) -> FusedState:
    # jnp.array (not asarray): the state is donated on every step, so it
    # must own its buffers — aliasing a caller's med/mad array would
    # delete the caller's copy on the first dispatch
    return FusedState(index=index,
                      halo=jnp.zeros((halo_samples,), jnp.float32),
                      med=jnp.array(med), mad=jnp.array(mad))


def init_pool_state(indexes: list[IndexState], halo_samples: int,
                    meds, mads) -> FusedState:
    """Stack per-station pieces into one pool state (leading S axis)."""
    n = len(indexes)
    return FusedState(
        index=index_mod.stack_states(indexes),
        halo=jnp.zeros((n, halo_samples), jnp.float32),
        med=jnp.stack([jnp.asarray(m) for m in meds]),
        mad=jnp.stack([jnp.asarray(m) for m in mads]))


def _chunk_core(index: IndexState, med: jax.Array, mad: jax.Array,
                wave: jax.Array, mappings: jax.Array, base_id: jax.Array,
                valid: jax.Array | None, fcfg: FingerprintConfig,
                lcfg: LSHConfig, window: int, saturation: int = 0,
                dup_tables: int = 0, occ_limit: int = 0, counters: int = 0,
                max_pairs: int = 0, verify: int = 0, min_jac: float = 0.0
                ) -> tuple[IndexState, Pairs, jax.Array]:
    """One station's block: fingerprint → hash → expire → guards →
    insert → query.

    Shared by the solo and the vmapped pool entries; bit-identical to the
    unfused ``block_coeffs`` + ``stream_step`` chain (the parity test's
    contract). Signatures and bucket addresses are computed together once
    (``signatures_and_buckets``) instead of once in insert and again in
    query. The data-quality guards (duplicate probe, bucket-saturation
    quarantine, in-dispatch §6.5 occurrence limiter —
    ``index.guarded_step``) run inside this same traced program: with the
    knobs at 0 they compile away and the step is the pre-quality program
    exactly. Returns the per-step counter vector ``qc`` (layout
    ``index.QC_FIELDS``: guard counters + the ISSUE-6 telemetry counters,
    the latter live only when ``counters`` is set) alongside pairs.

    ``max_pairs``/``verify``/``min_jac`` (ISSUE 8) enable the emission
    epilogue inside the same dispatch: the dense pair stream is compacted
    to ``(max_pairs,)`` and, with ``verify``, scored with exact Jaccard —
    the bit-packed fingerprints the binarizer already produces feed the
    ``IndexState.pk`` ring, so fingerprint → hash → bucket → query →
    verify → compact is literally one fused device program.

    Each stage runs under a ``jax.named_scope`` (``fingerprint``,
    ``hash`` here; ``expire``, ``dup_guard``, ``insert``, ``query``,
    ``limit``, ``compact``, ``verify`` in ``guarded_step``), so every
    device operation of the step carries its stage in its op metadata.
    """
    with jax.named_scope("fingerprint"):
        coeffs = fp_mod.coeffs_from_waveform(wave, fcfg)
        bits, packed = fp_mod.binarize_coeffs(coeffs, fcfg, (med, mad))
    n = bits.shape[0]
    with jax.named_scope("hash"):
        sigs, buckets = lsh_mod.signatures_and_buckets(
            bits, mappings, lcfg, index.shape[1], valid=valid)
        ids = base_id + jnp.arange(n, dtype=jnp.int32)
    return index_mod.guarded_step(index, sigs, buckets, ids, valid, lcfg,
                                  window, saturation=saturation,
                                  dup_tables=dup_tables,
                                  occ_limit=occ_limit, counters=counters,
                                  packed=packed if verify > 0 else None,
                                  max_pairs=max_pairs, verify=verify,
                                  min_jac=min_jac)


_QUALITY_STATICS = ("fcfg", "lcfg", "window", "saturation",
                    "dup_tables", "occ_limit", "counters",
                    "max_pairs", "verify", "min_jac")


@functools.partial(jax.jit, static_argnames=_QUALITY_STATICS,
                   donate_argnums=(0,))
def step_advance(state: FusedState, new_samples: jax.Array,
                 mappings: jax.Array, base_id: jax.Array,
                 fcfg: FingerprintConfig, lcfg: LSHConfig,
                 window: int = 0, saturation: int = 0, dup_tables: int = 0,
                 occ_limit: int = 0, counters: int = 0, max_pairs: int = 0,
                 verify: int = 0, min_jac: float = 0.0
                 ) -> tuple[FusedState, Pairs, jax.Array]:
    """Steady-state fused step: device halo + new samples → pairs.

    ``new_samples`` is (advance,) = block_fingerprints * lag_samples; the
    block is reassembled on device from the donated halo, and the new halo
    (the block tail) is written back in place.
    """
    wave = jnp.concatenate([state.halo, new_samples])
    index, pairs, qc = _chunk_core(state.index, state.med, state.mad, wave,
                                   mappings, base_id, None, fcfg, lcfg,
                                   window, saturation, dup_tables,
                                   occ_limit, counters, max_pairs, verify,
                                   min_jac)
    return FusedState(index=index, halo=wave[-state.halo.shape[-1]:],
                      med=state.med, mad=state.mad), pairs, qc


@functools.partial(jax.jit, static_argnames=_QUALITY_STATICS,
                   donate_argnums=(0,))
def step_block(state: FusedState, block: jax.Array, mappings: jax.Array,
               base_id: jax.Array, valid: jax.Array,
               fcfg: FingerprintConfig, lcfg: LSHConfig,
               window: int = 0, saturation: int = 0, dup_tables: int = 0,
               occ_limit: int = 0, counters: int = 0, max_pairs: int = 0,
               verify: int = 0, min_jac: float = 0.0
               ) -> tuple[FusedState, Pairs, jax.Array]:
    """Re-seeding fused step: a whole framed block + fingerprint mask.

    Used for the first block after a freeze/restore, for gap-masked
    blocks (fingerprints whose window overlaps missing data are
    suppressed in-dispatch), and for masked flush tails; also reprimes
    the halo so the next step can take the advance path (a zero-padded
    tail leaves the halo dirty — the caller tracks that and routes the
    next block through here again; a gap-masked but fully framed block
    leaves it primed).
    """
    index, pairs, qc = _chunk_core(state.index, state.med, state.mad, block,
                                   mappings, base_id, valid, fcfg, lcfg,
                                   window, saturation, dup_tables,
                                   occ_limit, counters, max_pairs, verify,
                                   min_jac)
    return FusedState(index=index, halo=block[-state.halo.shape[-1]:],
                      med=state.med, mad=state.mad), pairs, qc


@functools.partial(jax.jit, static_argnames=_QUALITY_STATICS,
                   donate_argnums=(0,))
def pool_step_advance(state: FusedState, new_samples: jax.Array,
                      mappings: jax.Array, base_id: jax.Array,
                      fcfg: FingerprintConfig, lcfg: LSHConfig,
                      window: int = 0, saturation: int = 0,
                      dup_tables: int = 0, occ_limit: int = 0,
                      counters: int = 0, max_pairs: int = 0,
                      verify: int = 0, min_jac: float = 0.0
                      ) -> tuple[FusedState, Pairs, jax.Array]:
    """``step_advance`` over a station pool: state leaves and
    ``new_samples`` carry a leading (S,) axis; ids/base advance in
    lockstep (stations ingest the same chunk cadence)."""
    wave = jnp.concatenate([state.halo, new_samples], axis=-1)
    core = functools.partial(_chunk_core, fcfg=fcfg, lcfg=lcfg,
                             window=window, saturation=saturation,
                             dup_tables=dup_tables, occ_limit=occ_limit,
                             counters=counters, max_pairs=max_pairs,
                             verify=verify, min_jac=min_jac)
    index, pairs, qc = jax.vmap(core, in_axes=(0, 0, 0, 0, None, None,
                                               None))(
        state.index, state.med, state.mad, wave, mappings, base_id, None)
    return FusedState(index=index, halo=wave[:, -state.halo.shape[-1]:],
                      med=state.med, mad=state.mad), pairs, qc


@functools.partial(jax.jit, static_argnames=_QUALITY_STATICS,
                   donate_argnums=(0,))
def pool_step_block(state: FusedState, blocks: jax.Array,
                    mappings: jax.Array, base_id: jax.Array,
                    valid: jax.Array, fcfg: FingerprintConfig,
                    lcfg: LSHConfig, window: int = 0, saturation: int = 0,
                    dup_tables: int = 0, occ_limit: int = 0,
                    counters: int = 0, max_pairs: int = 0,
                    verify: int = 0, min_jac: float = 0.0
                    ) -> tuple[FusedState, Pairs, jax.Array]:
    """``step_block`` over a station pool (blocks (S, block_samples),
    valid (S, block_fingerprints) — per-station gap masks differ when one
    station drops out while the others keep streaming)."""
    core = functools.partial(_chunk_core, fcfg=fcfg, lcfg=lcfg,
                             window=window, saturation=saturation,
                             dup_tables=dup_tables, occ_limit=occ_limit,
                             counters=counters, max_pairs=max_pairs,
                             verify=verify, min_jac=min_jac)
    index, pairs, qc = jax.vmap(core, in_axes=(0, 0, 0, 0, None, None, 0))(
        state.index, state.med, state.mad, blocks, mappings, base_id, valid)
    return FusedState(index=index, halo=blocks[:, -state.halo.shape[-1]:],
                      med=state.med, mad=state.mad), pairs, qc


# ---------------------------------------------------------------------------
# sharded station pool (ISSUE 10): the same pool entries over a device mesh
# ---------------------------------------------------------------------------
#
# The leading S axis of every FusedState leaf is split over the mesh's
# ``stations`` axis via ``jax.shard_map``; inside the region each device
# runs the identical vmapped per-station core over its own S/D rows. The
# hot path has **zero** cross-station communication (association is a
# host tail), so the region is fully manual and needs no collectives.
#
# ``mappings`` and ``base_id`` are replicated (every station hashes with
# the same tables and ingests the same block cadence); all outputs carry
# the station axis, so pair emission stays one ``device_get`` of a
# station-sharded buffer. Entries are cached per (mesh, statics) — the
# one-dispatch invariant's retracing half holds exactly as in the vmap
# pool (≤1 steady-state trace per entry, pinned by tests).

_SHARDED_ENTRIES: dict = {}


def _mesh_width(mesh) -> int:
    return int(mesh.devices.size) if mesh is not None else 1


def _sharded_entry(mesh, advance: bool, statics: tuple):
    key = (mesh, advance, statics)
    fn = _SHARDED_ENTRIES.get(key)
    if fn is not None:
        return fn
    (fcfg, lcfg, window, saturation, dup_tables, occ_limit, counters,
     max_pairs, verify, min_jac) = statics
    core = functools.partial(_chunk_core, fcfg=fcfg, lcfg=lcfg,
                             window=window, saturation=saturation,
                             dup_tables=dup_tables, occ_limit=occ_limit,
                             counters=counters, max_pairs=max_pairs,
                             verify=verify, min_jac=min_jac)
    axis = mesh.axis_names[0]
    if advance:
        def body(state, new_samples, mappings, base_id):
            wave = jnp.concatenate([state.halo, new_samples], axis=-1)
            index, pairs, qc = jax.vmap(
                core, in_axes=(0, 0, 0, 0, None, None, None))(
                state.index, state.med, state.mad, wave, mappings,
                base_id, None)
            return FusedState(index=index,
                              halo=wave[:, -state.halo.shape[-1]:],
                              med=state.med, mad=state.mad), pairs, qc

        in_specs = (P(axis), P(axis), P(), P())
    else:
        def body(state, blocks, mappings, base_id, valid):
            index, pairs, qc = jax.vmap(
                core, in_axes=(0, 0, 0, 0, None, None, 0))(
                state.index, state.med, state.mad, blocks, mappings,
                base_id, valid)
            return FusedState(index=index,
                              halo=blocks[:, -state.halo.shape[-1]:],
                              med=state.med, mad=state.mad), pairs, qc

        in_specs = (P(axis), P(axis), P(), P(), P(axis))
    sharded = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                            out_specs=(P(axis), P(axis), P(axis)),
                            axis_names={axis})
    fn = jax.jit(sharded, donate_argnums=(0,))
    _SHARDED_ENTRIES[key] = fn
    return fn


def pool_step_advance_sharded(state: FusedState, new_samples: jax.Array,
                              mappings: jax.Array, base_id: jax.Array,
                              fcfg: FingerprintConfig, lcfg: LSHConfig,
                              window: int = 0, saturation: int = 0,
                              dup_tables: int = 0, occ_limit: int = 0,
                              counters: int = 0, max_pairs: int = 0,
                              verify: int = 0, min_jac: float = 0.0, *,
                              mesh=None
                              ) -> tuple[FusedState, Pairs, jax.Array]:
    """``pool_step_advance`` with the station axis split over ``mesh``.

    Falls back to the single-device vmap pool when ``mesh`` is absent or
    1-device, or when the pool width does not divide the mesh (the
    caller pads the pool — ``dist.padded_pool_width`` — so hitting the
    fallback means the pool was built without this mesh in hand). The
    fallback is bit-identical: the sharded region runs the same vmapped
    per-station core, just split across devices."""
    if _mesh_width(mesh) < 2 or state.halo.shape[0] % _mesh_width(mesh):
        return pool_step_advance(state, new_samples, mappings, base_id,
                                 fcfg, lcfg, window, saturation,
                                 dup_tables, occ_limit, counters,
                                 max_pairs, verify, min_jac)
    statics = (fcfg, lcfg, window, saturation, dup_tables, occ_limit,
               counters, max_pairs, verify, min_jac)
    return _sharded_entry(mesh, True, statics)(state, new_samples,
                                               mappings, base_id)


def pool_step_block_sharded(state: FusedState, blocks: jax.Array,
                            mappings: jax.Array, base_id: jax.Array,
                            valid: jax.Array, fcfg: FingerprintConfig,
                            lcfg: LSHConfig, window: int = 0,
                            saturation: int = 0, dup_tables: int = 0,
                            occ_limit: int = 0, counters: int = 0,
                            max_pairs: int = 0, verify: int = 0,
                            min_jac: float = 0.0, *, mesh=None
                            ) -> tuple[FusedState, Pairs, jax.Array]:
    """``pool_step_block`` over a ``stations`` mesh axis (see
    ``pool_step_advance_sharded`` for the fallback contract)."""
    if _mesh_width(mesh) < 2 or state.halo.shape[0] % _mesh_width(mesh):
        return pool_step_block(state, blocks, mappings, base_id, valid,
                               fcfg, lcfg, window, saturation, dup_tables,
                               occ_limit, counters, max_pairs, verify,
                               min_jac)
    statics = (fcfg, lcfg, window, saturation, dup_tables, occ_limit,
               counters, max_pairs, verify, min_jac)
    return _sharded_entry(mesh, False, statics)(state, blocks, mappings,
                                                base_id, valid)
