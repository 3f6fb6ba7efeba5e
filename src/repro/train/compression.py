"""Cross-pod gradient compression (distributed-optimization trick).

The pod axis is DCN (slow links); an fp32 ring all-reduce of the gradients
costs 2×4 bytes/param across it. Here the pod reduction is made EXPLICIT:
a partial-manual ``shard_map`` keeps data/model axes automatic (the inner
computation still SPMD-partitions normally) while the pod axis is manual,
and the gradient exchange becomes an int8 all-gather + local dequant-mean —
(P-1)/P × 1 byte/param of DCN traffic, an ~8× reduction.

Quantization is per-tensor absmax int8 (round-to-nearest). With 2 pods the
quantization error is an unbiased-ish dither on the half-gradient;
EXPERIMENTS.md §Perf carries the convergence check.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import dist


def _quantize(g):
    """Per-tensor absmax int8 quantization with a leading pod-stack axis."""
    gf = g.astype(jnp.float32)
    scale = jnp.max(jnp.abs(gf)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
    return q[None], scale[None]


def compressed_pod_mean(tree):
    """Mean of a gradient pytree across the manual 'pod' axis via int8.

    In-region variant (requires a runtime whose partitioner supports
    collectives inside manual subgroups; jaxlib 0.4.x CPU does not — the
    shard_map wrapper below routes the exchange through a reshard instead).
    """
    def one(g):
        if g.dtype == jnp.int32 or g.ndim == 0:
            return jax.lax.pmean(g, "pod")
        q, scale = _quantize(g)
        qs = jax.lax.all_gather(q[0], "pod")       # int8 on the wire
        ss = jax.lax.all_gather(scale[0], "pod")   # (P,) fp32 scales
        deq = qs.astype(jnp.float32) * ss.reshape(
            (-1,) + (1,) * g.ndim)
        return deq.mean(axis=0).astype(g.dtype)

    return jax.tree.map(one, tree)


def pod_compressed_value_and_grad(loss_fn, mesh, batch_spec_prefix=P("pod")):
    """value_and_grad whose cross-pod gradient exchange is int8.

    ``loss_fn(params, batch) -> scalar`` must compute the mean loss over
    its (pod-local) batch shard. Returns f(params, batch) -> (loss, grads)
    with grads exact over data/model (automatic) and int8-compressed over
    pod (manual).

    The exchange itself happens *outside* the manual region: the partial-
    manual body returns each pod's quantized gradients stacked over a
    leading ``pod``-sharded axis, and a reshard-to-replicated constraint on
    the int8 tensors lowers to exactly the s8 all-gather we want on the DCN
    links (an in-region ``lax.all_gather`` trips the SPMD partitioner's
    manual-subgroup check on current jaxlib).
    """
    def _exempt(leaf) -> bool:
        # integer / scalar grads take the exact pmean path (quantizing an
        # int32 or a lone scalar to absmax-int8 is lossy garbage)
        return jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.integer) \
            or jnp.ndim(leaf) == 0

    def per_pod(params, batch):
        with dist.manual_axes({"pod"}):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            loss = jax.lax.pmean(loss, "pod")
            q = jax.tree.map(
                lambda g: jax.lax.pmean(g, "pod") if _exempt(g)
                else _quantize(g)[0], grads)
            s = jax.tree.map(
                lambda g: jnp.zeros((1,), jnp.float32) if _exempt(g)
                else _quantize(g)[1], grads)
        return loss, q, s

    from jax.sharding import NamedSharding

    def wrapped(params, batch):
        in_specs = (P(), jax.tree.map(lambda _: batch_spec_prefix, batch))
        out_specs = (
            P(),
            jax.tree.map(lambda p: P() if _exempt(p) else P("pod"), params),
            jax.tree.map(lambda p: P() if _exempt(p) else P("pod"), params))
        loss, q, s = jax.shard_map(
            per_pod, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            axis_names={"pod"}, check_vma=False)(params, batch)

        def dequant_mean(g, qv, sv):
            if _exempt(g):
                return qv                  # already the exact pod mean
            # (P, *shape) int8 sharded over pod → replicate (s8 all-gather)
            qv = jax.lax.with_sharding_constraint(
                qv, NamedSharding(mesh, P()))
            sv = jax.lax.with_sharding_constraint(
                sv, NamedSharding(mesh, P()))
            deq = qv.astype(jnp.float32) * sv.reshape(
                (-1,) + (1,) * (qv.ndim - 1))
            return deq.mean(axis=0).astype(g.dtype)

        grads = jax.tree.map(dequant_mean, params, q, s)
        return loss, grads

    return wrapped
