"""Mesh-aware sharding helpers.

Model code calls ``shard(x, *axes)`` to attach sharding constraints; the
helpers degrade to no-ops when no mesh is active (single-device tests) and
silently drop axes that do not divide the corresponding dimension (e.g.
8 KV heads on a 16-way ``model`` axis → replicated). Axes made manual by a
partial-manual shard_map (train/compression.py) are dropped from specs
inside the manual region via the ``manual_axes`` context.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Sequence

import jax
import numpy as np
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec as P

# mesh axis name of the station-pool shard (stream/fused.py): the leading
# S axis of the stacked FusedState pytree is split over it
STATION_AXIS = "stations"

_MANUAL: contextvars.ContextVar[frozenset] = contextvars.ContextVar(
    "repro_manual_axes", default=frozenset())

_UNEVEN: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "repro_allow_uneven", default=False)

# Layout mode: "tp" (default, megatron TP over 'model') or "fsdp"
# (pure data parallelism over pod×data×model; params fully sharded and
# gathered per use — the §Perf layout for large-batch dense training).
_LAYOUT: contextvars.ContextVar[str] = contextvars.ContextVar(
    "repro_layout", default="tp")


@contextlib.contextmanager
def layout(mode: str):
    assert mode in ("tp", "fsdp"), mode
    tok = _LAYOUT.set(mode)
    try:
        yield
    finally:
        _LAYOUT.reset(tok)


def current_layout() -> str:
    return _LAYOUT.get()


@contextlib.contextmanager
def manual_axes(axes):
    tok = _MANUAL.set(_MANUAL.get() | frozenset(axes))
    try:
        yield
    finally:
        _MANUAL.reset(tok)


def in_manual_region() -> bool:
    """True while tracing inside a partial-manual shard_map region."""
    return bool(_MANUAL.get())


@contextlib.contextmanager
def allow_uneven_sharding():
    """Permit non-divisible dims (≥ axis size) to shard — XLA pads.

    §Perf lever: e.g. qwen2.5's 40 heads on a 16-way model axis would
    otherwise replicate ALL attention compute."""
    tok = _UNEVEN.set(True)
    try:
        yield
    finally:
        _UNEVEN.reset(tok)


def station_mesh(n_stations: int | None = None, *, devices=None,
                 axis: str = STATION_AXIS) -> Mesh | None:
    """Capability probe for the sharded station pool (ISSUE 10).

    Returns a 1-axis ``stations`` mesh over the visible devices when
    sharding the pool can possibly help, and ``None`` otherwise — the
    ``None`` is the signal for callers (``StreamingDetector``, the
    ``pool_step_*_sharded`` entries) to fall back to the single-device
    ``vmap`` pool:

    * one visible device → ``None`` (vmap already is the whole story);
    * fewer than two stations → ``None`` (nothing to split);
    * more devices than stations → the mesh is trimmed to ``n_stations``
      so no device holds an empty shard.

    The hot path runs **fully manual** over this axis with zero
    cross-station collectives.
    """
    devs = list(devices) if devices is not None else jax.devices()
    nd = len(devs)
    if n_stations is not None:
        nd = min(nd, int(n_stations))
    if nd < 2 or (n_stations is not None and n_stations < 2):
        return None
    return Mesh(np.asarray(devs[:nd]), (axis,))


def pool_sharding(mesh: Mesh, *, axis: str = STATION_AXIS) -> NamedSharding:
    """Sharding of a stacked pool pytree: leading (S,) axis split over
    ``stations``, everything else replicated (usable as a pytree-prefix
    sharding for every FusedState leaf)."""
    return NamedSharding(mesh, P(axis))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully-replicated placement on ``mesh`` (hash mappings, scalars)."""
    return NamedSharding(mesh, P())


def padded_pool_width(n_stations: int, mesh: Mesh | None, *,
                      axis: str = STATION_AXIS) -> int:
    """Station rows the stacked pool must carry so the leading axis
    divides the mesh: ``n_stations`` rounded up to a multiple of the
    ``stations`` axis size (``n_stations`` unchanged without a mesh).
    The pad rows are throwaway station clones — they step like real
    stations (row-independent math) and their output is never read."""
    if mesh is None or axis not in mesh.shape:
        return int(n_stations)
    d = int(mesh.shape[axis])
    return -(-int(n_stations) // d) * d


def current_mesh() -> AbstractMesh | None:
    """The mesh installed by ``jax.set_mesh`` (readable inside ``jit``
    too), or None."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def axis_size(name: str) -> int:
    mesh = current_mesh()
    if mesh is None or name not in mesh.shape:
        return 1
    return mesh.shape[name]


def batch_axes() -> tuple[str, ...]:
    """Mesh axes used for data parallelism.

    TP layout: pod × data. FSDP layout: pod × data × model (the model
    axis joins the batch; tensor-parallel constraints become no-ops)."""
    mesh = current_mesh()
    if mesh is None:
        return ()
    names = (("pod", "data", "model") if _LAYOUT.get() == "fsdp"
             else ("pod", "data"))
    return tuple(a for a in names if a in mesh.shape)


def dp_size() -> int:
    out = 1
    for a in batch_axes():
        out *= axis_size(a)
    return out


def _entry_size(entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, str):
        return axis_size(entry)
    out = 1
    for a in entry:
        out *= axis_size(a)
    return out


def sanitize_spec(shape: Sequence[int], spec: Sequence) -> P | None:
    """Drop spec entries that don't exist on the mesh or don't divide."""
    mesh = current_mesh()
    if mesh is None:
        return None
    out = []
    for dim, entry in zip(shape, spec):
        if entry is None:
            out.append(None)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        # axis aliases: "vocab" always resolves to the model axis (vocab
        # sharding survives FSDP); a bare "model" entry is a TP usage and
        # drops under the FSDP layout (the axis belongs to the batch there)
        if _LAYOUT.get() == "fsdp":
            axes = tuple("model" if a == "vocab" else a for a in axes
                         if a != "model")
        else:
            axes = tuple("model" if a == "vocab" else a for a in axes)
        # keep the subset of axes present on this mesh (e.g. ("pod","data")
        # degrades to ("data",) on the single-pod mesh); manual axes are
        # invisible to constraints inside shard_map regions
        manual = _MANUAL.get()
        axes = tuple(a for a in axes if a in mesh.shape and a not in manual)
        if not axes:
            out.append(None)
            continue
        if dim % _entry_size(axes) != 0 and not (
                _UNEVEN.get() and dim >= _entry_size(axes)):
            out.append(None)
            continue
        out.append(axes[0] if len(axes) == 1 else axes)
    # pad remaining dims
    out += [None] * (len(shape) - len(out))
    return P(*out)


def shard(x: jax.Array, *spec):
    """with_sharding_constraint that no-ops without a mesh / on misfit.

    Passes a raw PartitionSpec so the constraint resolves against the
    CONTEXT mesh — correct both in plain jit and inside partial-manual
    shard_map regions (where the concrete mesh's axis types mismatch).
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    p = sanitize_spec(x.shape, spec)
    if p is None:
        return x
    return jax.lax.with_sharding_constraint(x, p)


def shard_batch(x: jax.Array, *rest):
    """Shard the leading (batch) dim over pod×data, rest as given."""
    ba = batch_axes()
    if not ba:
        return x
    return shard(x, ba, *rest)
