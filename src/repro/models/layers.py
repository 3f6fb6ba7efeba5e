"""Transformer building blocks: RMSNorm, RoPE, blocked attention, SwiGLU,
MoE with expert parallelism.

Sharding convention (DESIGN.md §4): activations (B, S, D) shard B over
pod×data; attention heads / FFN hidden / experts / vocab shard over
``model``. KV-head and expert dims that don't divide the model axis fall
back to replication (dist.sanitize_spec).
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import dist
from repro.models.config import ModelConfig


# ---------------------------------------------------------------------------
# norms / rope / embeddings
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(dt)


def rope_tables(positions: jax.Array, head_dim: int,
                theta: float) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables (..., head_dim/2) for given positions."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, hd); cos/sin: (B, S, hd/2) or (S, hd/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def embed_tokens(table: jax.Array, tokens: jax.Array,
                 cfg: ModelConfig) -> jax.Array:
    out = jnp.take(table.astype(cfg.cdtype), tokens, axis=0)
    return dist.shard_batch(out, None, None)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def qkv_project(params: dict, x: jax.Array, cfg: ModelConfig,
                positions: jax.Array):
    """x (B,S,D) → q (B,S,Hq,hd), k/v (B,S,Hkv,hd) with RoPE applied."""
    b, s, d = x.shape
    hd = cfg.hd
    q = jnp.einsum("bsd,dhk->bshk", x,
                   params["wq"].astype(cfg.cdtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(cfg.cdtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(cfg.cdtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(cfg.cdtype)
        k = k + params["bk"].astype(cfg.cdtype)
        v = v + params["bv"].astype(cfg.cdtype)
    cos, sin = rope_tables(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    q = dist.shard_batch(q, None, "model", None)
    k = dist.shard_batch(k, None, "model", None)
    v = dist.shard_batch(v, None, "model", None)
    return q, k, v


def _attend_block(q, k, v, mask, scale):
    """One (bq × bk) online-softmax update. All fp32 accumulation."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, -1e30)
    return s


def blocked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      cfg: ModelConfig, *, causal: bool = True,
                      impl: str = "masked") -> jax.Array:
    """Memory-bounded causal attention with online softmax.

    q: (B, S, Hq, hd); k/v: (B, S, Hkv, hd). Scores never exceed
    (B, Hq, bq, bk). ``impl='masked'`` runs all KV blocks with masking
    (simple, 2× causal FLOPs); ``impl='triangular'`` unrolls query blocks
    and visits only allowed KV blocks (the §Perf compute optimization).
    """
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    bq = min(cfg.attn_q_block, s)
    bk = min(cfg.attn_kv_block, s)
    nq, nk = s // bq, s // bk
    scale = 1.0 / math.sqrt(hd)
    qT = jnp.swapaxes(q, 1, 2)  # (B, Hq, S, hd)
    kT = jnp.swapaxes(jnp.repeat(k, group, axis=2), 1, 2)
    vT = jnp.swapaxes(jnp.repeat(v, group, axis=2), 1, 2)
    kT = dist.shard_batch(kT, "model", None, None)
    vT = dist.shard_batch(vT, "model", None, None)

    # jaxlib 0.4.x partial-manual regions cannot partition scans whose
    # bodies gather region inputs with traced starts (see decoder.
    # _scan_blocks) — unroll both loops there; static slices are fine.
    unroll = dist.in_manual_region()

    def q_block(iq, qblk):
        # qblk: (B, Hq, bq, hd)
        def _kv_math(carry, kblk, vblk, ik):
            acc, m, l = carry
            qi = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            ki = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = (ki <= qi) if causal else jnp.ones((bq, bk), bool)
            sc = _attend_block(qblk, kblk, vblk, mask[None, None], scale)
            m_new = jnp.maximum(m, sc.max(-1))
            p = jnp.exp(sc - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(-1)
            pv = jnp.einsum("bhqk,bhkd->bhqd", p.astype(vblk.dtype), vblk,
                            preferred_element_type=jnp.float32)
            acc_new = acc * alpha[..., None] + pv
            return acc_new, m_new, l_new

        kv_math = functools.partial(jax.checkpoint,
                                    prevent_cse=False)(_kv_math)

        @functools.partial(jax.checkpoint, prevent_cse=False)
        def kv_step(carry, ik):
            # slices INSIDE the remat: the kv scan stores only (carry, ik)
            # per step, never a second full copy of kT/vT
            kblk = jax.lax.dynamic_slice_in_dim(kT, ik * bk, bk, axis=2)
            vblk = jax.lax.dynamic_slice_in_dim(vT, ik * bk, bk, axis=2)
            return _kv_math(carry, kblk, vblk, ik), None

        acc0 = jnp.zeros((b, hq, bq, hd), jnp.float32)
        m0 = jnp.full((b, hq, bq), -1e30, jnp.float32)
        l0 = jnp.zeros((b, hq, bq), jnp.float32)
        if impl == "triangular" and causal:
            n_allowed = int(iq) * bq // bk + 1  # static per unrolled block
        else:
            n_allowed = nk
        if unroll:
            carry = (acc0, m0, l0)
            for ik in range(n_allowed):
                kblk = jax.lax.slice_in_dim(kT, ik * bk, (ik + 1) * bk,
                                            axis=2)
                vblk = jax.lax.slice_in_dim(vT, ik * bk, (ik + 1) * bk,
                                            axis=2)
                carry = kv_math(carry, kblk, vblk, jnp.int32(ik))
            acc, m, l = carry
        else:
            (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0),
                                          jnp.arange(n_allowed))
        return (acc / jnp.maximum(l[..., None], 1e-20)).astype(q.dtype)

    if (impl == "triangular" and causal) or unroll:
        outs = [q_block(i, jax.lax.slice_in_dim(qT, i * bq, (i + 1) * bq,
                                                axis=2))
                for i in range(nq)]
        out = jnp.concatenate(outs, axis=2)
    else:
        qblocks = qT.reshape(b, hq, nq, bq, hd).transpose(2, 0, 1, 3, 4)
        out = jax.lax.map(lambda args: q_block(args[0], args[1]),
                          (jnp.arange(nq), qblocks))
        out = out.transpose(1, 2, 0, 3, 4).reshape(b, hq, s, hd)
    return jnp.swapaxes(out, 1, 2)  # (B, S, Hq, hd)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Single-token attention against a (B, Skv, Hkv, hd) cache.

    The cache's sequence dim is sharded over ``model`` (distributed
    flash-decode): XLA turns the softmax max/sum and the weighted sum into
    three small all-reduces (DESIGN.md §4).
    """
    b, one, hq, hd = q.shape
    hkv = k_cache.shape[2]
    group = hq // hkv
    kx = jnp.repeat(k_cache, group, axis=2)
    vx = jnp.repeat(v_cache, group, axis=2)
    # pin the flash-decode layout: cache stays sequence-sharded with heads
    # replicated — otherwise XLA reshards the (huge) cache toward the
    # head-sharded o_proj instead of resharding the (tiny) output
    kx = dist.shard_batch(kx, "model", None, None)
    vx = dist.shard_batch(vx, "model", None, None)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kx,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    ki = jnp.arange(k_cache.shape[1])[None, None, None, :]
    s = jnp.where(ki <= pos[:, None, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w.astype(vx.dtype), vx,
                     preferred_element_type=jnp.float32)
    out = dist.shard_batch(out, None, None, None)
    return out.astype(q.dtype)


def attention_block(params: dict, x: jax.Array, cfg: ModelConfig,
                    positions: jax.Array, *, impl: str = "masked",
                    return_kv: bool = False):
    """Full pre-norm attention residual block (training / prefill)."""
    h = rms_norm(x, params["ln"], cfg.rms_eps)
    q, k, v = qkv_project(params, h, cfg, positions)
    o = blocked_attention(q, k, v, cfg, impl=impl)
    o = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(cfg.cdtype))
    o = dist.shard_batch(o, None, None)
    if return_kv:
        return x + o, (k, v)
    return x + o


def attention_block_decode(params: dict, x: jax.Array, cache: dict,
                           pos: jax.Array, cfg: ModelConfig):
    """Decode-step attention block; updates the KV cache in place.

    x: (B, 1, D); cache: {"k": (B, S, Hkv, hd), "v": ...}; pos: (B,) int32.
    """
    h = rms_norm(x, params["ln"], cfg.rms_eps)
    q, k_new, v_new = qkv_project(params, h, cfg, pos[:, None])
    # flash-decode layout: q heads REPLICATED across model (decode flops
    # are negligible); the cache keeps its sequence dim sharded so the
    # softmax reductions become three small all-reduces — avoids the
    # heads-vs-sequence sharding conflict XLA otherwise resolves with an
    # all-gather of the cache.
    q = dist.shard_batch(q, None, None, None)
    if cfg.uniform_decode_pos:
        # one shared position → dynamic-update-slice, which the SPMD
        # partitioner handles on the seq-sharded cache without gathering
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k_new.astype(cache["k"].dtype),
            (0, pos[0], 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v_new.astype(cache["v"].dtype),
            (0, pos[0], 0, 0))
    else:
        # per-slot positions (continuous batching): batched scatter
        bidx = jnp.arange(x.shape[0])
        k_cache = cache["k"].at[bidx, pos].set(
            k_new[:, 0].astype(cache["k"].dtype))
        v_cache = cache["v"].at[bidx, pos].set(
            v_new[:, 0].astype(cache["v"].dtype))
    k_cache = dist.shard_batch(k_cache, "model", None, None)
    v_cache = dist.shard_batch(v_cache, "model", None, None)
    o = decode_attention(q, k_cache, v_cache, pos, cfg)
    o = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(cfg.cdtype))
    return x + o, {"k": k_cache, "v": v_cache}


def parallel_attn_mlp_block(attn_params: dict, mlp_params: dict,
                            x: jax.Array, cfg: ModelConfig,
                            positions: jax.Array, *, impl: str = "masked",
                            cache: dict | None = None,
                            pos: jax.Array | None = None,
                            return_kv: bool = False):
    """Command-r-style parallel block: y = x + attn(ln(x)) + mlp(ln(x)).

    Both sub-blocks produce TP partial sums that are ADDED before a single
    sharding constraint, so XLA emits ONE all-reduce per layer instead of
    two — half the TP activation traffic (§Perf) and faithful to the
    upstream architecture.
    """
    h = rms_norm(x, attn_params["ln"], cfg.rms_eps)
    extra = None
    if cache is not None:  # decode
        q, k_new, v_new = qkv_project(attn_params, h, cfg, pos[:, None])
        q = dist.shard_batch(q, None, None, None)
        if cfg.uniform_decode_pos:
            k_cache = jax.lax.dynamic_update_slice(
                cache["k"], k_new.astype(cache["k"].dtype),
                (0, pos[0], 0, 0))
            v_cache = jax.lax.dynamic_update_slice(
                cache["v"], v_new.astype(cache["v"].dtype),
                (0, pos[0], 0, 0))
        else:
            bidx = jnp.arange(x.shape[0])
            k_cache = cache["k"].at[bidx, pos].set(
                k_new[:, 0].astype(cache["k"].dtype))
            v_cache = cache["v"].at[bidx, pos].set(
                v_new[:, 0].astype(cache["v"].dtype))
        k_cache = dist.shard_batch(k_cache, "model", None, None)
        v_cache = dist.shard_batch(v_cache, "model", None, None)
        o = decode_attention(q, k_cache, v_cache, pos, cfg)
        extra = {"k": k_cache, "v": v_cache}
    else:
        q, k, v = qkv_project(attn_params, h, cfg, positions)
        o = blocked_attention(q, k, v, cfg, impl=impl)
        if return_kv:
            extra = (k, v)
    ao = jnp.einsum("bshk,hkd->bsd", o, attn_params["wo"].astype(cfg.cdtype))
    g = jnp.einsum("bsd,df->bsf", h, mlp_params["wg"].astype(cfg.cdtype))
    u = jnp.einsum("bsd,df->bsf", h, mlp_params["wu"].astype(cfg.cdtype))
    g = dist.shard_batch(g, None, "model")
    u = dist.shard_batch(u, None, "model")
    mo = jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                    mlp_params["wd"].astype(cfg.cdtype))
    y = x + dist.shard_batch(ao + mo, None, None)   # single psum
    if extra is not None or return_kv:
        return y, extra
    return y


# ---------------------------------------------------------------------------
# dense MLP (SwiGLU)
# ---------------------------------------------------------------------------


def mlp_block(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    h = rms_norm(x, params["ln"], cfg.rms_eps)
    g = jnp.einsum("bsd,df->bsf", h, params["wg"].astype(cfg.cdtype))
    u = jnp.einsum("bsd,df->bsf", h, params["wu"].astype(cfg.cdtype))
    g = dist.shard_batch(g, None, "model")
    u = dist.shard_batch(u, None, "model")
    y = jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                   params["wd"].astype(cfg.cdtype))
    return x + dist.shard_batch(y, None, None)


# ---------------------------------------------------------------------------
# MoE (shared + routed experts, EP over the model axis)
# ---------------------------------------------------------------------------


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    c = int(math.ceil(n_tokens * cfg.moe_top_k * cfg.capacity_factor
                      / cfg.n_experts))
    return max(8, c)


def _route(h2: jax.Array, router_w: jax.Array, cfg: ModelConfig):
    """(T, D) tokens → (top-k expert ids, combine weights, aux loss)."""
    logits = jnp.einsum("td,de->te", h2.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, cfg.moe_top_k)  # (T, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    # load-balance auxiliary (Switch-style)
    density = jnp.mean(jax.nn.one_hot(top_e[:, 0], cfg.n_experts), axis=0)
    p_mean = probs.mean(axis=0)
    aux = cfg.n_experts * jnp.sum(density * p_mean)
    return top_e.astype(jnp.int32), top_w, aux


def _rank_within_expert(flat_e: jax.Array, n_experts: int) -> jax.Array:
    """Arrival rank of each (token, slot) within its expert, O(T·k) memory."""
    tk = flat_e.shape[0]
    chunk = 8
    rank = jnp.zeros((tk,), jnp.int32)
    for e0 in range(0, n_experts, chunk):
        onehot = (flat_e[:, None] == jnp.arange(e0, e0 + chunk)[None, :])
        csum = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
        rank = rank + jnp.where(onehot, csum, 0).sum(axis=1)
    return rank


def _moe_local(h2: jax.Array, top_e: jax.Array, top_w: jax.Array,
               wg: jax.Array, wu: jax.Array, wd: jax.Array,
               e_base: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Dispatch→grouped GEMM→combine for the locally held experts.

    h2: (T, D); wg/wu: (E_loc, D, F); wd: (E_loc, F, D). ``e_base`` is the
    first global expert id held locally. Returns partial output (T, D)
    covering tokens routed to local experts (others zero).
    """
    t, d = h2.shape
    e_loc = wg.shape[0]
    k = cfg.moe_top_k
    cap = _capacity(t, cfg)
    flat_e = top_e.reshape(-1)                     # (T*k,) global ids
    rank = _rank_within_expert(flat_e, cfg.n_experts)
    local_e = flat_e - e_base
    ok = (local_e >= 0) & (local_e < e_loc) & (rank < cap)
    le = jnp.where(ok, local_e, 0)
    rr = jnp.where(ok, rank, cap)                  # cap → dropped
    src = jnp.repeat(h2, k, axis=0)                # (T*k, D)
    buf = jnp.zeros((e_loc, cap + 1, d), h2.dtype)
    buf = buf.at[le, rr].add(src, mode="drop")
    buf = buf[:, :cap]
    g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(h2.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(h2.dtype))
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                   wd.astype(h2.dtype))            # (E_loc, cap, D)
    y = jnp.concatenate([y, jnp.zeros((e_loc, 1, d), y.dtype)], axis=1)
    gathered = y[le, rr]                           # (T*k, D)
    gathered = jnp.where(ok[:, None], gathered, 0)
    w = top_w.reshape(-1)[:, None].astype(h2.dtype)
    return (gathered * w).reshape(t, k, d).sum(axis=1)


def moe_block(params: dict, x: jax.Array, cfg: ModelConfig):
    """Shared-expert + routed-expert MoE block.

    Routed experts are sharded over ``model`` (EP). Activations are
    replicated over ``model`` (they're sharded over pod×data only), so the
    EP combine is a single psum — the same collective volume as a TP MLP
    (DESIGN.md §4). Returns (y, aux_loss).
    """
    b, s, d = x.shape
    h = rms_norm(x, params["ln"], cfg.rms_eps)
    h2 = h.reshape(b * s, d)
    mesh = dist.current_mesh()
    use_ep = (mesh is not None and "model" in mesh.shape
              and cfg.n_experts % mesh.shape["model"] == 0)

    if use_ep:
        tp = mesh.shape["model"]
        e_loc = cfg.n_experts // tp
        ba = dist.batch_axes()

        def per_shard(h2s, rw, wg, wu, wd):
            top_e, top_w, aux = _route(h2s, rw, cfg)
            e_base = jax.lax.axis_index("model") * e_loc
            y = _moe_local(h2s, top_e, top_w, wg, wu, wd, e_base, cfg)
            y = jax.lax.psum(y, "model")
            # per-DATA-shard balance loss averaged over the whole mesh
            # (standard device-level balance objective; identical across
            # model shards, differs per data shard)
            all_axes = tuple(mesh.axis_names)
            aux = jax.lax.pmean(aux, all_axes)
            return y, aux

        spec_h = P(ba if ba else None, None)
        out = jax.shard_map(
            per_shard, mesh=mesh,
            in_specs=(spec_h, P(None, None), P("model", None, None),
                      P("model", None, None), P("model", None, None)),
            out_specs=(spec_h, P()),
            check_vma=False,
        )(h2, params["router"], params["wg"], params["wu"], params["wd"])
        y, aux = out
    else:
        top_e, top_w, aux = _route(h2, params["router"], cfg)
        y = _moe_local(h2, top_e, top_w, params["wg"], params["wu"],
                       params["wd"], jnp.int32(0), cfg)
    y = y.reshape(b, s, d)
    # shared experts: dense SwiGLU over all tokens
    if cfg.n_shared_experts > 0:
        g = jnp.einsum("bsd,df->bsf", h, params["swg"].astype(cfg.cdtype))
        u = jnp.einsum("bsd,df->bsf", h, params["swu"].astype(cfg.cdtype))
        g = dist.shard_batch(g, None, "model")
        u = dist.shard_batch(u, None, "model")
        y = y + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                           params["swd"].astype(cfg.cdtype))
    return x + dist.shard_batch(y, None, None), aux
