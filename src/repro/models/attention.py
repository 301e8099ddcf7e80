"""GQA attention block: train/prefill (flash) and decode (policy-dispatched).

The decode path is where FIER lives: the per-layer cache slice carries the
packed 1-bit side-car, and attention is dispatched through
``repro.core.policy`` — or, when the cache is sequence-sharded across mesh
axes, through the distributed LSE-merge path (``repro.core.distributed``)
inside a ``shard_map``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import distributed as dist
from repro.core import policy as core_policy
from repro.core.policy import CacheView, DecodePlan, PolicyConfig
from repro.kvcache import cache as kvcache
from repro.kvcache import paged as kvcache_paged
from repro.kvcache import sharded as kvcache_sharded

from .layers import apply_rope, flash_attention, init_linear, rms_norm, wuse


@dataclasses.dataclass(frozen=True)
class DistConfig:
    """How the model runs across the mesh.

    seq_axes: mesh axes the KV-cache *sequence* dim is sharded over at
    decode; empty tuple → single-shard policy path.  mode: 'local' |
    'exact' (see core.distributed).  ep_axis: mesh axis for MoE expert
    parallelism in train/prefill (shard_map path); fsdp_axes: axes expert
    weights are FSDP-stored over (gathered inside the EP body).
    """

    mesh: Any = None
    seq_axes: tuple[str, ...] = ()
    mode: str = "local"
    batch_axes: tuple[str, ...] = ()
    ep_axis: str | None = None
    fsdp_axes: tuple[str, ...] = ()
    # mesh sharding spec for the *paged* pool (kvcache.sharded.ShardSpec):
    # TP over KV heads × DP over slots.  Threaded into DecodePlan.build so
    # the plan carries it into decode_attention; None = single device
    shard: Any = None


def seq_shard_constraint(h: jax.Array, dcfg: "DistConfig | None") -> jax.Array:
    """Megatron-style sequence-parallel activation sharding: the residual
    stream between layers is sharded [batch→batch_axes, seq→'model'].

    This is what the layer-scan remat *saves*, so it bounds activation-
    checkpoint memory at L·B·S·d/(data·model) instead of /(data) — the
    difference between 155 GB and ~10 GB per device on qwen3-moe train_4k
    (EXPERIMENTS.md §Perf iteration 2).  XLA inserts the all-gather before
    attention and the reduce-scatter after, exactly as in Megatron-SP.
    """
    if dcfg is None or dcfg.mesh is None or "model" not in dcfg.mesh.axis_names:
        return h
    if "model" in dcfg.batch_axes:  # fsdp_pure: batch spans 'model' already
        return h
    if h.ndim < 2 or h.shape[1] % dcfg.mesh.shape["model"]:
        return h
    bd = tuple(dcfg.batch_axes) if dcfg.batch_axes else None
    from jax.sharding import NamedSharding

    return jax.lax.with_sharding_constraint(
        h, NamedSharding(dcfg.mesh, P(bd, "model"))
    )


def init_attention(rng: jax.Array, cfg: ModelConfig, d_in: int | None = None) -> dict:
    d = d_in if d_in is not None else cfg.d_model
    kq, kk, kv, ko = jax.random.split(rng, 4)
    p = {
        "wq": init_linear(kq, d, cfg.n_heads * cfg.d_head),
        "wk": init_linear(kk, d, cfg.n_kv_heads * cfg.d_head),
        "wv": init_linear(kv, d, cfg.n_kv_heads * cfg.d_head),
        "wo": init_linear(ko, cfg.n_heads * cfg.d_head, cfg.d_model),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * cfg.d_head,), jnp.float32)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * cfg.d_head,), jnp.float32)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * cfg.d_head,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.d_head,), jnp.float32)
        p["k_norm"] = jnp.ones((cfg.d_head,), jnp.float32)
    return p


def _proj(x: jax.Array, w: jax.Array, b: jax.Array | None) -> jax.Array:
    y = x @ wuse(w, -1).astype(x.dtype)
    if b is not None:
        y = y + b.astype(x.dtype)
    return y


def qkv_proj(
    p: dict, x: jax.Array, cfg: ModelConfig, positions: jax.Array | None
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x: [B, S, d] → q [B,S,Hq,D], k/v [B,S,Hkv,D] (QK-norm, then RoPE,
    applied: the cache holds the normed keys)."""
    B, S, _ = x.shape
    q = _proj(x, p["wq"], p.get("bq")).reshape(B, S, cfg.n_heads, cfg.d_head)
    k = _proj(x, p["wk"], p.get("bk")).reshape(B, S, cfg.n_kv_heads, cfg.d_head)
    v = _proj(x, p["wv"], p.get("bv")).reshape(B, S, cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.use_rope:
        if positions is None:
            positions = jnp.arange(S, dtype=jnp.int32)[None, :]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_train(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    causal: bool = True,
    kv_x: jax.Array | None = None,
    positions: jax.Array | None = None,
    block_k: int = 512,
) -> jax.Array:
    """Full (flash) attention for train/prefill; ``kv_x`` → cross-attention."""
    B, S, _ = x.shape
    if kv_x is None:
        q, k, v = qkv_proj(p, x, cfg, positions)
    else:
        Sk = kv_x.shape[1]
        q = _proj(x, p["wq"], p.get("bq")).reshape(B, S, cfg.n_heads, cfg.d_head)
        k = _proj(kv_x, p["wk"], p.get("bk")).reshape(B, Sk, cfg.n_kv_heads, cfg.d_head)
        v = _proj(kv_x, p["wv"], p.get("bv")).reshape(B, Sk, cfg.n_kv_heads, cfg.d_head)
    o = flash_attention(q, k, v, causal=causal, block_k=block_k)
    return o.reshape(B, S, cfg.n_heads * cfg.d_head) @ wuse(p["wo"], 0).astype(x.dtype)


# ------------------------------------------------------------------- decode

def decode_self_attention(
    p: dict,
    x: jax.Array,
    layer_cache: dict,
    length: jax.Array,
    cfg: ModelConfig,
    plan: DecodePlan | PolicyConfig,
    dcfg: DistConfig | None = None,
    *,
    update_meta: bool = True,
    block_table: jax.Array | None = None,
) -> tuple[jax.Array, dict]:
    """One-token decode self-attention with cache append + plan-dispatched
    attention.

    x: [B, 1, d]; layer_cache: {k, v[, meta]} (single layer, no L axis);
    length: [B] current lengths (the new token is written at ``length``).
    ``plan`` is the resolved ``DecodePlan`` (a bare ``PolicyConfig`` is
    wrapped via ``DecodePlan.build`` as a convenience).  Returns
    (out [B, 1, d], updated layer_cache).

    ``block_table`` [B, n_btab] switches the layer to the *paged* cache:
    layer_cache holds block-pool slabs [N, bs, Hkv, D] (+ paged side-car)
    shared by all requests, the append and the metadata refresh write
    through the table, and attention dispatches through a paged
    ``CacheView`` to the page-table-aware kernels.

    When the cache is sequence-sharded (dcfg.seq_axes), the append, the
    metadata refresh AND the attention all run inside one shard_map — a
    traced-index dynamic_update_slice along a GSPMD-sharded dim would
    otherwise all-gather the whole slab (observed: 2.13 GB/chip/layer on
    the first dry-run; EXPERIMENTS.md §Perf iteration 1).  This path is
    its own reference implementation (``core.distributed`` LSE merge):
    the single-shard kernel pipelines never run under GSPMD.
    """
    if isinstance(plan, PolicyConfig):
        plan = DecodePlan.build(plan)
    pol = plan.policy
    B = x.shape[0]
    q, k_new, v_new = qkv_proj(p, x, cfg, positions=length[:, None])
    qh = q.reshape(B, cfg.n_heads, cfg.d_head)
    meta = layer_cache.get("meta")

    if block_table is not None:
        if dcfg is not None and dcfg.seq_axes:
            raise ValueError(
                "paged KV cache + sequence-sharded decode is not supported; "
                "shard the paged pool over the mesh instead "
                "(Engine.build(mesh=...) → kvcache.sharded)"
            )
        spec = getattr(plan, "shard", None)
        if spec is not None:
            out, k_pool, v_pool, meta = kvcache_sharded.sharded_paged_decode_step(
                qh, k_new, v_new, layer_cache["k"], layer_cache["v"], meta,
                block_table, length, pol, plan, spec, update_meta=update_meta,
            )
        else:
            k_pool, v_pool = kvcache_paged.paged_append_kv(
                layer_cache["k"], layer_cache["v"], k_new, v_new,
                block_table, length,
            )
            if meta is not None and update_meta:
                meta = kvcache_paged.paged_append_token_metadata(
                    meta, k_pool, block_table, length, pol
                )
            view = CacheView.paged(k_pool, v_pool, meta, block_table, length + 1)
            out = core_policy.decode_attention(
                qh, view, plan, layer=pol.skip_layers
            )
        new_cache = dict(layer_cache, k=k_pool, v=v_pool)
        if meta is not None:
            new_cache["meta"] = meta
        y = out.reshape(B, 1, cfg.n_heads * cfg.d_head) @ wuse(p["wo"], 0).astype(x.dtype)
        return y, new_cache

    if dcfg is not None and dcfg.seq_axes:
        out, k_slab, v_slab, meta = _sharded_decode_step(
            qh, k_new, v_new, layer_cache["k"], layer_cache["v"], meta,
            length, cfg, pol, dcfg,
        )
    else:
        k_slab, v_slab = kvcache.append_kv(
            layer_cache["k"], layer_cache["v"], k_new, v_new, length
        )
        if meta is not None and update_meta:
            meta = kvcache.append_token_metadata(meta, k_slab, length, pol)
        view = CacheView.slab(k_slab, v_slab, meta, length + 1)
        out = core_policy.decode_attention(
            qh, view, plan, layer=pol.skip_layers
        )
    new_cache = dict(layer_cache, k=k_slab, v=v_slab)
    if meta is not None:
        new_cache["meta"] = meta
    y = out.reshape(B, 1, cfg.n_heads * cfg.d_head) @ wuse(p["wo"], 0).astype(x.dtype)
    return y, new_cache


def _sharded_decode_step(
    q: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    K: jax.Array,
    V: jax.Array,
    meta: Any,
    length: jax.Array,
    cfg: ModelConfig,
    pol: PolicyConfig,
    dcfg: DistConfig,
):
    """Sequence-sharded decode: shard-local append + metadata refresh +
    distributed FIER (or full) attention with LSE merge.  The only
    collective is the O(Hq·D) psum of partial attention outputs (plus the
    small candidate all-gather in mode='exact')."""
    mesh = dcfg.mesh
    axes = dcfg.seq_axes
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    bspec = tuple(dcfg.batch_axes) if dcfg.batch_axes else None

    kv_spec = P(bspec, axes)
    q_spec = P(bspec)
    S = K.shape[1]
    S_loc = S // n_shards
    g = pol.group if pol.kind == "fier" else 0

    def body(q_l, kn_l, vn_l, K_l, V_l, meta_l, len_l):
        idx = jnp.int32(0)
        mul = 1
        for a in reversed(axes):
            idx = idx + jax.lax.axis_index(a) * mul
            mul *= mesh.shape[a]
        shard_start = idx * S_loc

        # ---- shard-local append: only the owning shard commits the write.
        # The select happens on the 1-row update value, never on the slab
        # (a slab-wide where() copies the whole cache per layer per token,
        # and XLA:CPU additionally promotes it to f32 — §Perf iteration 6).
        rel = len_l - shard_start                       # [B]
        owns = (rel >= 0) & (rel < S_loc)
        wpos = jnp.clip(rel, 0, S_loc - 1)
        read_row = jax.vmap(
            lambda c, i: jax.lax.dynamic_slice_in_dim(c, i, 1, axis=0)
        )
        upd = jax.vmap(
            lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(c, n, i, axis=0)
        )
        ow = owns[:, None, None, None]
        kw = jnp.where(ow, kn_l.astype(K_l.dtype), read_row(K_l, wpos))
        vw = jnp.where(ow, vn_l.astype(V_l.dtype), read_row(V_l, wpos))
        K2 = upd(K_l, kw, wpos)
        V2 = upd(V_l, vw, wpos)

        # ---- shard-local metadata refresh (group containing the write)
        meta2 = meta_l
        if meta_l is not None and pol.kind == "fier":
            meta2 = kvcache.append_token_metadata(
                meta_l, K2, wpos, pol, commit_mask=owns
            )

        new_len = len_l + 1
        if pol.kind == "fier" and meta2 is not None:
            out = dist.fier_decode_sharded(
                q_l, K2, V2, meta2, pol.budget, new_len,
                axis=axes, shard_start=shard_start, n_shards=n_shards,
                group_reduce=pol.group_reduce, mode=dcfg.mode,
            )
        else:
            out = dist.full_decode_sharded(
                q_l, K2, V2, new_len, axis=axes, shard_start=shard_start
            )
        return out, K2, V2, meta2

    meta_spec = jax.tree.map(lambda _: kv_spec, meta)
    f = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(q_spec, q_spec, q_spec, kv_spec, kv_spec, meta_spec, q_spec),
        out_specs=(q_spec, kv_spec, kv_spec, meta_spec),
        check_vma=False,
    )
    return f(q, k_new, v_new, K, V, meta, length)
