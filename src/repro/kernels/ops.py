"""jit'd wrappers adapting cache layouts to the head-major Pallas kernels.

On the CPU backend (tests, CPU rehearsals) kernels run in
``interpret=True`` mode — the kernel body executes as Python/jnp,
validating the exact code that compiles for TPU.  Every other backend
compiles them with Mosaic (``interpret=False``): a backend Mosaic cannot
target fails loudly instead of being interpreted in silence.

Layout note: the cache is seq-major [B, S, H, D] (sequence sharding);
kernels want head-major [B·H, S, D] so the scan streams contiguously.
The transposes below are the *baseline* of the slab layout; the paged
pool is read in place.

Cache-layout dispatch happens on :class:`repro.core.policy.CacheView`:
``retrieve`` / ``attend_selected`` read the slab-vs-paged choice off
``view.layout`` instead of forking into ``fused_*`` / ``paged_fused_*``
entrypoint pairs (those names remain as deprecation shims below).
``fier_decode_one_pass`` / ``fier_decode_two_pass`` are the kernel
pipelines the ``fier`` backend registers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.policy import CacheView, _warn_deprecated
from repro.core.quantize import QuantizedKeys

from . import fier_score as _fs
from . import fused_retrieval as _fr
from . import pack_quantize as _pq
from . import sparse_attention as _sa
from . import topk_select as _tk


# selected rows per fused select-and-attend grid step: each row's K and V
# land in VMEM with all Hkv heads (2·Hkv·D·2 B), so 128 rows keep the
# scratch and its f32 temporaries near 1 MiB at Hkv = 16, D = 128
ATTEND_BLK = 128


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def fier_score(q: jax.Array, qk: QuantizedKeys, *, blk_s: int = 512) -> jax.Array:
    """Packed 1-bit score scan.  q [B,Hq,D], qk seq-major → f32 [B,Hq,S]."""
    B, Hq, D = q.shape
    Hkv = qk.codes.shape[2]
    rep = Hq // Hkv
    S = qk.codes.shape[1] * 8
    qhm = q.reshape(B, Hkv, rep, D).reshape(B * Hkv, rep, D)
    to_hm = lambda a: jnp.moveaxis(a, 2, 1).reshape(B * Hkv, a.shape[1], D)
    out = _fs.fier_score_hm(
        qhm, to_hm(qk.codes), to_hm(qk.scale), to_hm(qk.zero),
        group=qk.group, blk_s=min(blk_s, S), interpret=_interpret(),
    )
    return out.reshape(B, Hkv, rep, S).reshape(B, Hq, S)


def sparse_attention(
    q: jax.Array,
    k_sel: jax.Array,
    v_sel: jax.Array,
    idx: jax.Array,
    length: jax.Array | None,
    *,
    blk_k: int = 1024,
) -> jax.Array:
    """Decode attention over selected tokens.

    q [B,Hq,D]; k_sel/v_sel [B,k,Hkv,D]; idx [B,Hkv,k]; length [B]
    → [B,Hq,D] (q.dtype).
    """
    B, Hq, D = q.shape
    k = k_sel.shape[1]
    Hkv = k_sel.shape[2]
    rep = Hq // Hkv
    qhm = q.reshape(B, Hkv, rep, D).reshape(B * Hkv, rep, D)
    khm = jnp.moveaxis(k_sel, 2, 1).reshape(B * Hkv, k, D)
    vhm = jnp.moveaxis(v_sel, 2, 1).reshape(B * Hkv, k, D)
    if length is not None:
        valid = idx < length[:, None, None]
    else:
        valid = jnp.ones_like(idx, dtype=bool)
    mask = valid.reshape(B * Hkv, 1, k).astype(jnp.int8)
    out = _sa.sparse_attention_hm(
        qhm, khm, vhm, mask, blk_k=min(blk_k, k), interpret=_interpret()
    )
    return out.reshape(B, Hkv, rep, D).reshape(B, Hq, D).astype(q.dtype)


def pack_quantize(k: jax.Array, group: int, *, blk_s: int = 512) -> QuantizedKeys:
    """Quantize+pack a seq-major key slab [B,S,Hkv,D] → QuantizedKeys."""
    B, S, H, D = k.shape
    khm = jnp.moveaxis(k, 2, 1).reshape(B * H, S, D)
    codes, scale, zero = _pq.pack_quantize_hm(
        khm, group=group, blk_s=min(blk_s, S), interpret=_interpret()
    )
    back = lambda a: jnp.moveaxis(a.reshape(B, H, a.shape[1], D), 1, 2)
    return QuantizedKeys(back(codes), back(scale), back(zero), group)


def topk_select(
    kv_scores: jax.Array,
    budget: int,
    length: jax.Array | None = None,
    *,
    sink: int = 0,
    recent: int = 0,
    blk_s: int = 2048,
) -> jax.Array:
    """Threshold top-k selection — no global sort.

    kv_scores f32 [B, Hkv, S] → indices int32 [B, Hkv, budget]; same index
    set as ``retrieval.select_topk`` (the lax.top_k oracle) for any input.
    The [B·Hkv, S] reshape is a view (no copy): the kv-score layout is
    already head-major.
    """
    from repro.core import retrieval

    B, Hkv, S = kv_scores.shape
    s = retrieval.masked_scores(kv_scores, length, sink=sink, recent=recent)
    s = s.reshape(B * Hkv, S)
    tau, m = _tk.topk_threshold_hm(
        s, budget, blk_s=min(blk_s, S), interpret=_interpret()
    )
    idx = _tk.compact_indices(s, tau, m, budget)
    return idx.reshape(B, Hkv, budget)


# --------------------------------------------------- CacheView-based dispatch

def retrieve(
    q: jax.Array,
    view: CacheView,
    budget: int,
    *,
    group_reduce: str = "max",
    sink: int = 0,
    recent: int = 0,
    blk_s: int = 512,
    return_stats: bool = False,
):
    """One-pass retrieval over a ``CacheView``: packed codes →
    top-``budget`` *logical* token indices, with the per-token scores
    never materialised in HBM.

    q [B, Hq, D]; ``view.meta`` is the ``QuantizedKeys`` side-car (slab
    layout: seq-major [B, S/8, Hkv, D]; paged layout: pool
    [N, bs/8, Hkv, D] walked through ``view.block_table`` in-kernel) →
    idx int32 [B, Hkv, budget], the same index set as ``select_topk``
    over the masked, group-reduced ``fier_score`` scores.  One Pallas
    kernel streams the codes, scores each block in VREGs, group-reduces
    and masks in-register, radix-searches τ and compacts — neither the
    [B,Hq,S] nor the [B,Hkv,S] score tensor ever exists as an array.
    ``return_stats=True`` additionally returns (tau f32 [B,Hkv],
    m int32 [B,Hkv]) — the budget-th score and the strictly-greater
    count per row.
    """
    qk = view.meta
    length = view.length
    B, Hq, D = q.shape
    Hkv = qk.codes.shape[2]
    rep = Hq // Hkv
    if view.layout == "paged":
        block_size = qk.codes.shape[1] * 8
        n_btab = view.block_table.shape[1]
        S = n_btab * block_size
        q4 = q.reshape(B, Hkv, rep, D)
        if length is None:
            lens = jnp.full((B,), S, jnp.int32)
            recent = 0  # masked_scores applies `recent` only with a length
        else:
            lens = length.astype(jnp.int32)
        idx, tau, m = _fr.paged_fused_retrieve_hm(
            q4, qk.codes, qk.scale, qk.zero, view.block_table, lens, budget,
            group=qk.group, block_size=block_size, group_reduce=group_reduce,
            sink=sink, recent=recent, interpret=_interpret(),
        )
        if return_stats:
            return idx, tau, m
        return idx
    S = qk.seq_len
    qhm = q.reshape(B, Hkv, rep, D).reshape(B * Hkv, rep, D)
    to_hm = lambda a: jnp.moveaxis(a, 2, 1).reshape(B * Hkv, a.shape[1], D)
    if length is None:
        lens = jnp.full((B * Hkv,), S, jnp.int32)
        recent = 0  # masked_scores applies `recent` only with a length
    else:
        lens = jnp.broadcast_to(
            length.astype(jnp.int32)[:, None], (B, Hkv)
        ).reshape(B * Hkv)
    idx, tau, m = _fr.fused_retrieve_hm(
        qhm, to_hm(qk.codes), to_hm(qk.scale), to_hm(qk.zero), lens, budget,
        group=qk.group, blk_s=blk_s, group_reduce=group_reduce,
        sink=sink, recent=recent, interpret=_interpret(),
    )
    idx = idx.reshape(B, Hkv, budget)
    if return_stats:
        return idx, tau.reshape(B, Hkv), m.reshape(B, Hkv)
    return idx


def attend_selected(
    q: jax.Array,
    view: CacheView,
    idx: jax.Array,
    *,
    blk_k: int = ATTEND_BLK,
) -> jax.Array:
    """Fused select-and-attend over a ``CacheView``: the selected rows are
    gathered *inside* the kernel (per-row DMA; paged layout additionally
    translates logical→(block, offset) through ``view.block_table`` in
    SMEM), so no K'/V' copies — and nothing cache-sized — is ever
    materialised.

    q [B, Hq, D]; idx [B, Hkv, budget] logical positions → [B, Hq, D]
    (q.dtype).
    """
    B, Hq, D = q.shape
    Hkv = view.k.shape[2]
    rep = Hq // Hkv
    budget = idx.shape[2]
    length = view.length
    if length is not None:
        valid = idx < length[:, None, None]
    else:
        valid = jnp.ones_like(idx, dtype=bool)
    mask = valid[:, :, None, :].astype(jnp.int8)
    blk = min(blk_k, budget)
    while budget % blk:
        blk //= 2
    q4 = q.reshape(B, Hkv, rep, D)
    if view.layout == "paged":
        block_size = view.k.shape[1]
        out = _sa.paged_fused_sparse_attention_hm(
            q4, view.k, view.v, view.block_table, idx, mask,
            block_size=block_size, blk_k=blk, interpret=_interpret(),
        )
    else:
        out = _sa.fused_sparse_attention_hm(
            q4, view.k, view.v, idx, mask, blk_k=blk, interpret=_interpret()
        )
    return out.reshape(B, Hq, D).astype(q.dtype)


# --------------------------------------------------------- backend pipelines

def fier_decode_one_pass(
    q: jax.Array,
    view: CacheView,
    budget: int,
    *,
    group_reduce: str = "max",
    sink: int = 0,
    recent: int = 0,
    blk_k: int = ATTEND_BLK,
) -> jax.Array:
    """The ``one_pass`` FIER pipeline — the serving decode fast path for
    both layouts: single-kernel retrieval (per-token scores never in
    HBM) chained into the fused select-and-attend kernel.  Bit-identical
    to ``fier_decode_two_pass`` (same scores → same index set in the
    same compaction order → same attend kernel), and across layouts on
    the same logical cache contents."""
    idx = retrieve(
        q, view, budget, group_reduce=group_reduce, sink=sink, recent=recent
    )
    return attend_selected(q, view, idx, blk_k=blk_k)


def fier_decode_two_pass(
    q: jax.Array,
    view: CacheView,
    budget: int,
    *,
    group_reduce: str = "max",
    sink: int = 0,
    recent: int = 0,
    blk_k: int = ATTEND_BLK,
) -> jax.Array:
    """The ``two_pass`` FIER pipeline (slab layout only): score-scan
    kernel → threshold top-k kernel (f32 score tensors materialised
    between them) → fused select-and-attend.  Kept for ablation and the
    byte-accounting benchmarks."""
    from repro.core import retrieval

    if view.layout != "slab":
        raise ValueError("two_pass pipeline supports the slab layout only")
    Hkv = view.k.shape[2]
    scores = fier_score(q, view.meta)
    kv_scores = retrieval.reduce_over_query_group(scores, Hkv, group_reduce)
    idx = topk_select(
        kv_scores, budget, view.length, sink=sink, recent=recent
    )
    return attend_selected(q, view, idx, blk_k=blk_k)


# ---------------------------------------------------------- deprecated shims
# Pre-registry entrypoints: thin forwards onto the CacheView-based API,
# kept for external callers.  Each warns (DeprecationWarning) once per
# process on first call.

def fused_sparse_attention(
    q: jax.Array,
    K: jax.Array,
    V: jax.Array,
    idx: jax.Array,
    length: jax.Array | None,
    *,
    blk_k: int = ATTEND_BLK,
) -> jax.Array:
    """Deprecated: ``attend_selected(q, CacheView.slab(K, V), idx)``."""
    _warn_deprecated(
        "kernels.ops.fused_sparse_attention",
        "kernels.ops.attend_selected(q, CacheView.slab(K, V, length=length), idx)",
    )
    return attend_selected(
        q, CacheView.slab(K, V, length=length), idx, blk_k=blk_k
    )


def fused_retrieve(
    q: jax.Array,
    qk: QuantizedKeys,
    budget: int,
    length: jax.Array | None = None,
    *,
    group_reduce: str = "max",
    sink: int = 0,
    recent: int = 0,
    blk_s: int = 512,
    return_stats: bool = False,
):
    """Deprecated: ``retrieve(q, view, budget, ...)`` on a slab view."""
    _warn_deprecated(
        "kernels.ops.fused_retrieve",
        "kernels.ops.retrieve(q, CacheView.slab(..., meta=qk, length=length), budget)",
    )
    view = CacheView.slab(None, None, qk, length)
    return retrieve(
        q, view, budget, group_reduce=group_reduce, sink=sink, recent=recent,
        blk_s=blk_s, return_stats=return_stats,
    )


def fier_attention_decode(
    q: jax.Array,
    K: jax.Array,
    V: jax.Array,
    qk: QuantizedKeys,
    budget: int,
    length: jax.Array | None = None,
    *,
    group_reduce: str = "max",
) -> jax.Array:
    """Deprecated kernel-path unfused decode (kernel scoring + XLA top-k +
    materialised gather + kernel attend) — compose the building blocks or
    use a ``DecodePlan`` pipeline instead."""
    from repro.core import retrieval

    _warn_deprecated(
        "kernels.ops.fier_attention_decode",
        "policy.decode_attention(q, view, plan) or the fier_score / "
        "topk_select / sparse_attention building blocks",
    )
    Hkv = K.shape[2]
    scores = fier_score(q, qk)
    kv_scores = retrieval.reduce_over_query_group(scores, Hkv, group_reduce)
    idx = retrieval.select_topk(kv_scores, budget, length)
    k_sel, v_sel = retrieval.gather_kv(K, V, idx)
    return sparse_attention(q, k_sel, v_sel, idx, length)


def fused_fier_attention_decode(
    q: jax.Array,
    K: jax.Array,
    V: jax.Array,
    qk: QuantizedKeys,
    budget: int,
    length: jax.Array | None = None,
    *,
    group_reduce: str = "max",
    sink: int = 0,
    recent: int = 0,
    blk_k: int = ATTEND_BLK,
    one_pass: bool = True,
) -> jax.Array:
    """Deprecated: ``fier_decode_one_pass`` / ``fier_decode_two_pass`` on
    a slab ``CacheView`` (or ``policy.decode_attention`` with a plan)."""
    _warn_deprecated(
        "kernels.ops.fused_fier_attention_decode",
        "kernels.ops.fier_decode_one_pass / fier_decode_two_pass, or "
        "policy.decode_attention(q, view, plan)",
    )
    view = CacheView.slab(K, V, qk, length)
    fn = fier_decode_one_pass if one_pass else fier_decode_two_pass
    return fn(
        q, view, budget, group_reduce=group_reduce, sink=sink, recent=recent,
        blk_k=blk_k,
    )


# ------------------------------------------------- deprecated paged variants

def paged_fused_retrieve(
    q: jax.Array,
    meta: QuantizedKeys,
    block_table: jax.Array,
    budget: int,
    length: jax.Array | None = None,
    *,
    group_reduce: str = "max",
    sink: int = 0,
    recent: int = 0,
    return_stats: bool = False,
):
    """Deprecated: ``retrieve(q, view, budget, ...)`` on a paged view."""
    _warn_deprecated(
        "kernels.ops.paged_fused_retrieve",
        "kernels.ops.retrieve(q, CacheView.paged(..., meta, block_table, length), budget)",
    )
    view = CacheView.paged(None, None, meta, block_table, length)
    return retrieve(
        q, view, budget, group_reduce=group_reduce, sink=sink, recent=recent,
        return_stats=return_stats,
    )


def paged_fused_sparse_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_table: jax.Array,
    idx: jax.Array,
    length: jax.Array | None,
    *,
    blk_k: int = ATTEND_BLK,
) -> jax.Array:
    """Deprecated: ``attend_selected`` on a paged view."""
    _warn_deprecated(
        "kernels.ops.paged_fused_sparse_attention",
        "kernels.ops.attend_selected(q, CacheView.paged(k, v, None, block_table, length), idx)",
    )
    view = CacheView.paged(k_pool, v_pool, None, block_table, length)
    return attend_selected(q, view, idx, blk_k=blk_k)


def paged_fused_fier_attention_decode(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    meta: QuantizedKeys,
    block_table: jax.Array,
    budget: int,
    length: jax.Array | None = None,
    *,
    group_reduce: str = "max",
    sink: int = 0,
    recent: int = 0,
    blk_k: int = ATTEND_BLK,
) -> jax.Array:
    """Deprecated: ``fier_decode_one_pass`` on a paged ``CacheView`` (or
    ``policy.decode_attention`` with a paged plan)."""
    _warn_deprecated(
        "kernels.ops.paged_fused_fier_attention_decode",
        "kernels.ops.fier_decode_one_pass(q, CacheView.paged(...), budget) "
        "or policy.decode_attention(q, view, plan)",
    )
    view = CacheView.paged(k_pool, v_pool, meta, block_table, length)
    return fier_decode_one_pass(
        q, view, budget, group_reduce=group_reduce, sink=sink, recent=recent,
        blk_k=blk_k,
    )
