"""Pure-jnp oracles for every Pallas kernel (single source of truth: the
reference implementations in ``repro.core``).

Each function mirrors the layout of its ``ops.py`` counterpart exactly, so
tests can sweep shapes/dtypes and ``assert_allclose`` kernel-vs-oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import quantize as qz
from repro.core import retrieval
from repro.core.policy import CacheView, DecodePlan


def fier_score(q: jax.Array, qk: qz.QuantizedKeys) -> jax.Array:
    """[B,Hq,D] × QuantizedKeys([B,S/8,Hkv,D]) → f32 [B,Hq,S]."""
    return retrieval.approx_scores(q, qk)


def sparse_attention(
    q: jax.Array,
    k_sel: jax.Array,
    v_sel: jax.Array,
    idx: jax.Array,
    length: jax.Array | None,
) -> jax.Array:
    """[B,Hq,D] × selected [B,k,Hkv,D] → [B,Hq,D]."""
    return retrieval.sparse_attention(q, k_sel, v_sel, idx, length)


def pack_quantize(k: jax.Array, group: int) -> qz.QuantizedKeys:
    """[B,S,Hkv,D] → QuantizedKeys (codes/scale/zero, seq-major layout)."""
    return qz.quantize(k, group)


def topk_select(
    kv_scores: jax.Array,
    budget: int,
    length: jax.Array | None = None,
    *,
    sink: int = 0,
    recent: int = 0,
) -> jax.Array:
    """[B,Hkv,S] → int32 [B,Hkv,budget] — the lax.top_k global-sort oracle
    for the threshold-select kernel (index *sets* must match exactly)."""
    return retrieval.select_topk(
        kv_scores, budget, length, sink=sink, recent=recent
    )


def fused_retrieve(
    q: jax.Array,
    qk: qz.QuantizedKeys,
    budget: int,
    length: jax.Array | None = None,
    *,
    group_reduce: str = "max",
    sink: int = 0,
    recent: int = 0,
) -> jax.Array:
    """Oracle for the one-pass retrieval kernel: the fully-materialised
    jnp pipeline ``approx_scores → reduce_over_query_group → select_topk``
    (every intermediate score tensor written out, global lax.top_k sort).
    The kernel must return the same index *set* up to score-scan rounding;
    the *exact*-set contract is against select-over-``ops.fier_score``
    (bit-identical scores — see fier_score.score_block)."""
    Hkv = qk.codes.shape[2]
    s = retrieval.approx_scores(q, qk)
    kv = retrieval.reduce_over_query_group(s, Hkv, group_reduce)
    return retrieval.select_topk(kv, budget, length, sink=sink, recent=recent)


def fused_sparse_attention(
    q: jax.Array,
    K: jax.Array,
    V: jax.Array,
    idx: jax.Array,
    length: jax.Array | None,
) -> jax.Array:
    """Oracle for the fused kernel: materialised gather + sparse attention
    (the unfused pipeline the fused path must agree with to tolerance)."""
    k_sel, v_sel = retrieval.gather_kv(K, V, idx)
    return retrieval.sparse_attention(q, k_sel, v_sel, idx, length)


def blockwise_sparse_attention(
    q: jax.Array,
    K: jax.Array,
    V: jax.Array,
    idx: jax.Array,
    length: jax.Array | None,
    blk_k: int,
) -> jax.Array:
    """Oracle for the fused attend kernels' arithmetic: the selected rows'
    online softmax over budget blocks of ``blk_k`` rows, in order, bf16
    rows scored and summed in f32 with the kernels' expressions.

    q [B,Hq,D], K/V [B,S,Hkv,D], idx [B,Hkv,budget] → f32 [B,Hq,D].  One
    (batch, kv head) at a time, as the kernels' grid runs them: XLA rounds
    a batched dot differently."""
    B, Hq, D = q.shape
    Hkv, budget = idx.shape[1], idx.shape[2]
    rep = Hq // Hkv
    scale = 1.0 / (D**0.5)
    k_sel, v_sel = retrieval.gather_kv(K, V, idx)          # [B,budget,Hkv,D]
    valid = jnp.ones(idx.shape, bool) if length is None else idx < length[:, None, None]

    def one_head(a):
        qh, kh, vh, ok = a                                 # [rep,D], [budget,D] x2, [budget]
        m = jnp.full((rep,), -jnp.inf, jnp.float32)
        d = jnp.zeros((rep,), jnp.float32)
        out = jnp.zeros((rep, D), jnp.float32)
        for j in range(0, budget, blk_k):
            k = kh[j:j + blk_k].astype(jnp.float32)
            v = vh[j:j + blk_k].astype(jnp.float32)
            okj = ok[None, j:j + blk_k]
            s = jax.lax.dot_general(
                qh, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale
            s = jnp.where(okj, s, retrieval.NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(okj, jnp.exp(s - m_new[:, None]), 0.0)
            out = out * alpha[:, None] + jax.lax.dot(p, v, preferred_element_type=jnp.float32)
            d = d * alpha + p.sum(axis=-1)
            m = m_new
        return out / jnp.maximum(d, 1e-30)[:, None]

    out = jax.lax.map(one_head, (
        q.astype(jnp.float32).reshape(B * Hkv, rep, D),
        jnp.swapaxes(k_sel, 1, 2).reshape(B * Hkv, budget, D),
        jnp.swapaxes(v_sel, 1, 2).reshape(B * Hkv, budget, D),
        valid.reshape(B * Hkv, budget),
    ))
    return out.reshape(B, Hq, D)


# --------------------------------------------------- CacheView/plan oracles

def retrieve(
    q: jax.Array,
    view: CacheView,
    budget: int,
    *,
    group_reduce: str = "max",
    sink: int = 0,
    recent: int = 0,
) -> jax.Array:
    """Oracle for ``ops.retrieve``: materialise the logical side-car
    (paged layouts gather through the block table), then run the fully
    materialised jnp pipeline ``approx_scores → reduce_over_query_group →
    select_topk`` (global lax.top_k sort).  Same index *set* as the
    kernel for any input — the kernels' scores round identically."""
    _, _, meta = view.logical()
    Hkv = meta.codes.shape[2]
    s = retrieval.approx_scores(q, meta)
    kv = retrieval.reduce_over_query_group(s, Hkv, group_reduce)
    return retrieval.select_topk(
        kv, budget, view.length, sink=sink, recent=recent
    )


def decode_attention(q: jax.Array, view: CacheView, plan: DecodePlan) -> jax.Array:
    """The pure-jnp oracle for ``policy.decode_attention`` at *any*
    registered (policy, layout, pipeline): materialise the logical cache
    view and run the policy's reference pipeline with every intermediate
    written out.  The compatibility-matrix test (tests/test_backends.py)
    holds each plan's output to this: bit-identical for reference
    pipelines, exact index set + attend-kernel tolerance for the fused
    ones.

    Note the reference pipelines *are* these jnp building blocks, so for
    those matrix rows this oracle pins dispatch plumbing and the paged
    logical-gather, not the math — the math itself is anchored
    independently (``exact_scores`` / ``full_attention_decode``
    comparisons in tests/test_retrieval.py and the degenerate
    budget >= length cases)."""
    from repro.core import quest as quest_mod

    cfg = plan.policy
    K, V, meta = view.logical()
    length = view.length
    if cfg.kind == "full" or meta is None and cfg.kind != "slm":
        return retrieval.full_attention_decode(q, K, V, length)
    if cfg.kind == "fier":
        return retrieval.fier_decode_reference(
            q, K, V, meta, cfg.budget, length,
            group_reduce=cfg.group_reduce, sink=cfg.sink, recent=cfg.recent,
        )
    if cfg.kind == "quest":
        return quest_mod.quest_attention_decode(
            q, K, V, meta, cfg.budget, length, group_reduce=cfg.group_reduce
        )
    if cfg.kind == "slm":
        B, Hq, _ = q.shape
        Hkv = K.shape[2]
        sink = max(cfg.sink, 4)
        zeros = jnp.zeros((B, Hkv, K.shape[1]), jnp.float32)
        idx = retrieval.select_topk(
            zeros, cfg.budget, length, sink=sink, recent=cfg.budget - sink
        )
        Ksel, Vsel = retrieval.gather_kv(K, V, idx)
        return retrieval.sparse_attention(q, Ksel, Vsel, idx, length)
    raise ValueError(f"no oracle for policy {cfg.kind!r}")


# ------------------------------------------------------------- paged oracles

def paged_fused_retrieve(
    q: jax.Array,
    meta: qz.QuantizedKeys,
    block_table: jax.Array,
    budget: int,
    length: jax.Array | None = None,
    *,
    group_reduce: str = "max",
    sink: int = 0,
    recent: int = 0,
) -> jax.Array:
    """Oracle for the paged one-pass kernel: materialise the logical
    (table-gathered) side-car, then run the fully-materialised slab
    pipeline.  Same index-set contract as ``fused_retrieve``."""
    from repro.kvcache.paged import gather_block_rows

    logical = qz.QuantizedKeys(
        gather_block_rows(meta.codes, block_table),
        gather_block_rows(meta.scale, block_table),
        gather_block_rows(meta.zero, block_table),
        meta.group,
    )
    return fused_retrieve(
        q, logical, budget, length,
        group_reduce=group_reduce, sink=sink, recent=recent,
    )


def paged_fused_fier_attention_decode(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    meta: qz.QuantizedKeys,
    block_table: jax.Array,
    budget: int,
    length: jax.Array | None = None,
    *,
    group_reduce: str = "max",
    sink: int = 0,
    recent: int = 0,
) -> jax.Array:
    """Oracle for the paged fused decode: gather the logical K/V slab and
    side-car through the block table, then run the unfused jnp pipeline."""
    from repro.kvcache.paged import gather_paged_kv

    K, V, logical = gather_paged_kv(k_pool, v_pool, meta, block_table)
    Hkv = K.shape[2]
    s = retrieval.approx_scores(q, logical)
    kv = retrieval.reduce_over_query_group(s, Hkv, group_reduce)
    idx = retrieval.select_topk(kv, budget, length, sink=sink, recent=recent)
    k_sel, v_sel = retrieval.gather_kv(K, V, idx)
    return retrieval.sparse_attention(q, k_sel, v_sel, idx, length)


def held_experts_swiglu(
    x: jax.Array, gates: jax.Array, w1: jax.Array, w3: jax.Array, w2: jax.Array
) -> jax.Array:
    """Oracle for ``held_experts.held_experts_swiglu``: every held expert
    on every token, weighted by its gate (0 where the token does not route
    to it), as f32 [T, d]."""
    f32 = lambda a: a.astype(jnp.float32)
    a = jnp.einsum("td,edf->tef", f32(x), f32(w1))
    b = jnp.einsum("td,edf->tef", f32(x), f32(w3))
    h = jax.nn.silu(a) * b * f32(gates)[..., None]
    return jnp.einsum("tef,efd->td", h, f32(w2))
