"""FIER retrieval: approximate scores from 1-bit keys → top-k → exact attention.

Paper Algorithm 1, extended to batched GQA decode (the paper's "future work"
— see DESIGN.md §2).  All functions are pure and jit-friendly; the Pallas
fast path lives in ``repro.kernels`` and is validated against these.

Shapes (decode step):
    q        [B, Hq, D]          one new query per sequence
    K, V     [B, S, Hkv, D]      cache slabs (bf16)
    qk (side-car)                ``QuantizedKeys`` over the same slab
    length   [B] int32           valid prefix length per sequence
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .quantize import QuantizedKeys

NEG_INF = -1e30


APPROX_SCORE_BLOCK = 2048  # seq tokens per scan step (≈ a VMEM block)


def approx_scores(q: jax.Array, qk: QuantizedKeys) -> jax.Array:
    """s̃ = q·K̃ᵀ from packed 1-bit codes.  Returns f32 [B, Hq, S].

    For token i in seq-group G(i) the dequantized key is the level its
    code bit selects, k̃_i ∈ {z_G + s_G, z_G − s_G} rounded to bf16
    (``quantize.dequant_levels``), and s̃_i = bf16(q)·k̃_i accumulated in
    f32 — the expression the Pallas scorer evaluates
    (``kernels.fier_score.score_block``; DESIGN.md §One-pass).

    Computed *blockwise* over the sequence (lax.scan): the f32 unpack of
    the codes lives one block at a time, mirroring the kernel's
    HBM→VMEM streaming — the unblocked version materialised
    4·S·Hkv·D bytes per layer (gigabytes at 32k; §Perf iteration 5).
    """
    B, Hq, D = q.shape
    S = qk.seq_len
    g = qk.group
    blk = min(APPROX_SCORE_BLOCK, S)
    while S % blk:
        blk //= 2
    if blk == S:
        return _approx_scores_block(q, qk.codes, qk.scale, qk.zero, g)
    nb = S // blk
    codes = jnp.moveaxis(
        qk.codes.reshape(B, nb, blk // 8, *qk.codes.shape[2:]), 1, 0
    )
    scale = jnp.moveaxis(qk.scale.reshape(B, nb, blk // g, *qk.scale.shape[2:]), 1, 0)
    zero = jnp.moveaxis(qk.zero.reshape(B, nb, blk // g, *qk.zero.shape[2:]), 1, 0)

    def body(_, xs):
        c, s_, z_ = xs
        return None, _approx_scores_block(q, c, s_, z_, g)

    _, sb = jax.lax.scan(body, None, (codes, scale, zero))  # [nb, B, Hq, blk]
    return jnp.moveaxis(sb, 0, 2).reshape(B, Hq, S)


def _approx_scores_block(q, codes, scale, zero, g) -> jax.Array:
    """One block of the score contract: select each token's bf16 key level
    by its code bit, then a bf16 dot with f32 accumulation.  The levels
    round once (``dequant_levels``) and bf16×bf16 products are exact in
    f32, so only the f32 accumulation rounds — the block function is
    bit-stable eagerly, jitted, or as a ``lax.scan`` body
    (tests/test_retrieval.py::test_approx_scores_blockwise_independent_of_block)."""
    from .quantize import dequant_levels, unpack_bits

    B, Hq, D = q.shape
    S = codes.shape[1] * 8
    Hkv = codes.shape[2]
    rep = Hq // Hkv
    hi, lo = dequant_levels(scale, zero)                   # [B, S/g, Hkv, D]
    bits = unpack_bits(codes).reshape(B, S // g, g, Hkv, D)
    keys = jnp.where(bits == 1, hi[:, :, None], lo[:, :, None])
    qb = q.astype(jnp.bfloat16).reshape(B, Hkv, rep, D)
    s = jnp.einsum(
        "bhrd,bshd->bhrs", qb, keys.reshape(B, S, Hkv, D),
        preferred_element_type=jnp.float32,
    )
    return s.reshape(B, Hq, S)


def exact_scores(q: jax.Array, K: jax.Array) -> jax.Array:
    """Ground-truth scores q·Kᵀ (no softmax scaling — ranking only)."""
    B, Hq, D = q.shape
    Hkv = K.shape[2]
    rep = Hq // Hkv
    qf = q.astype(jnp.float32).reshape(B, Hkv, rep, D)
    s = jnp.einsum("bhrd,bshd->bhrs", qf, K.astype(jnp.float32))
    return s.reshape(B, Hq, -1)


def reduce_over_query_group(scores: jax.Array, n_kv: int, mode: str = "max") -> jax.Array:
    """GQA extension: [B, Hq, S] → [B, Hkv, S] so top-k is per KV head."""
    B, Hq, S = scores.shape
    s = scores.reshape(B, n_kv, Hq // n_kv, S)
    if mode == "max":
        return s.max(axis=2)
    if mode == "sum":
        return s.sum(axis=2)
    raise ValueError(f"unknown group reduction {mode!r}")


def masked_scores(
    scores: jax.Array,
    length: jax.Array | None = None,
    *,
    sink: int = 0,
    recent: int = 0,
) -> jax.Array:
    """Apply the selection guard-rails to raw scores [B, Hkv, S].

    ``length`` masks out unwritten cache slots (→ NEG_INF).  ``sink`` /
    ``recent`` force the first/last tokens into the selection by score
    override (+inf), the standard serving guard-rails; paper-faithful mode
    is sink=recent=0.  Shared by the jnp ``select_topk`` oracle and the
    Pallas threshold-select fast path so both rank the same scores.
    """
    B, Hkv, S = scores.shape
    pos = jnp.arange(S, dtype=jnp.int32)
    s = scores
    if length is not None:
        valid = pos[None, None, :] < length[:, None, None]
        s = jnp.where(valid, s, NEG_INF)
    if sink > 0:
        s = jnp.where(pos[None, None, :] < sink, jnp.inf, s)
    if recent > 0 and length is not None:
        is_recent = pos[None, None, :] >= (length - recent)[:, None, None]
        is_recent &= pos[None, None, :] < length[:, None, None]
        s = jnp.where(is_recent, jnp.inf, s)
    return s


def select_topk(
    scores: jax.Array,
    budget: int,
    length: jax.Array | None = None,
    *,
    sink: int = 0,
    recent: int = 0,
) -> jax.Array:
    """Top-``budget`` token indices per (batch, kv-head).

    scores: [B, Hkv, S] → indices int32 [B, Hkv, budget]

    This is the jnp oracle (global ``lax.top_k`` sort); the serving fast
    path is ``kernels.ops.topk_select`` (threshold search, no sort), which
    must return the same index *set* for any scores.
    """
    s = masked_scores(scores, length, sink=sink, recent=recent)
    _, idx = jax.lax.top_k(s, budget)
    return idx.astype(jnp.int32)


def gather_kv(K: jax.Array, V: jax.Array, idx: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Gather selected rows: K,V [B,S,Hkv,D], idx [B,Hkv,k] → [B,k,Hkv,D]."""
    Kh = jnp.swapaxes(K, 1, 2)  # [B,Hkv,S,D]
    Vh = jnp.swapaxes(V, 1, 2)
    Ksel = jnp.take_along_axis(Kh, idx[..., None], axis=2)
    Vsel = jnp.take_along_axis(Vh, idx[..., None], axis=2)
    return jnp.swapaxes(Ksel, 1, 2), jnp.swapaxes(Vsel, 1, 2)


def sparse_attention(
    q: jax.Array,
    Ksel: jax.Array,
    Vsel: jax.Array,
    idx: jax.Array,
    length: jax.Array | None = None,
) -> jax.Array:
    """Exact softmax attention over the selected tokens (decode, 1 query).

    q [B,Hq,D], Ksel/Vsel [B,k,Hkv,D], idx [B,Hkv,k] → out [B,Hq,D].
    Invalid slots (idx >= length, possible when budget > length) are masked.
    bf16 operands / f32 accumulation: `.astype(f32)` on the slabs would
    materialise f32 cache copies (§Perf iteration B — 2.3→0.9 GB/layer).
    """
    B, Hq, D = q.shape
    Hkv = Ksel.shape[2]
    rep = Hq // Hkv
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    qb = q.astype(Ksel.dtype).reshape(B, Hkv, rep, D)
    s = jnp.einsum(
        "bhrd,bkhd->bhrk", qb, Ksel, preferred_element_type=jnp.float32
    ) * scale
    if length is not None:
        invalid = idx[:, :, None, :] >= length[:, None, None, None]
        s = jnp.where(invalid, NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhrk,bkhd->bhrd", p.astype(Vsel.dtype), Vsel,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, Hq, D).astype(q.dtype)


def full_attention_decode(
    q: jax.Array, K: jax.Array, V: jax.Array, length: jax.Array | None = None
) -> jax.Array:
    """Dense decode attention over the whole cache (the Full-KV baseline).
    bf16 operands / f32 accumulation — see sparse_attention."""
    B, Hq, D = q.shape
    S, Hkv = K.shape[1], K.shape[2]
    rep = Hq // Hkv
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    qb = q.astype(K.dtype).reshape(B, Hkv, rep, D)
    s = jnp.einsum(
        "bhrd,bshd->bhrs", qb, K, preferred_element_type=jnp.float32
    ) * scale
    if length is not None:
        pos = jnp.arange(S, dtype=jnp.int32)
        valid = pos[None, None, None, :] < length[:, None, None, None]
        s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhrs,bshd->bhrd", p.astype(V.dtype), V,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, Hq, D).astype(q.dtype)


def fier_decode_reference(
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
    use_kernels: bool = False,
) -> jax.Array:
    """End-to-end FIER decode step (Alg. 1 steps 2–4) for batched GQA —
    the *reference* pipeline: score → ``select_topk`` → ``gather_kv`` →
    ``sparse_attention``, every intermediate materialised.  This is the
    validation oracle the kernel pipelines (``two_pass`` / ``one_pass``,
    see ``core.policy.DecodePlan``) are tested against, and the backend's
    ``pipeline='reference'`` implementation.  ``use_kernels=True`` swaps
    the scoring step for the Pallas score kernel (ablation; selection and
    attention stay jnp).
    """
    Hkv = K.shape[2]
    if use_kernels:
        from repro.kernels import ops as kops

        scores = kops.fier_score(q, qk)
    else:
        scores = approx_scores(q, qk)
    kv_scores = reduce_over_query_group(scores, Hkv, group_reduce)
    idx = select_topk(kv_scores, budget, length, sink=sink, recent=recent)
    Ksel, Vsel = gather_kv(K, V, idx)
    return sparse_attention(q, Ksel, Vsel, idx, length)


def fier_attention_decode(
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
    use_kernels: bool = False,
    fused: bool = False,
    one_pass: bool = True,
) -> jax.Array:
    """Deprecated boolean-flag entrypoint: forwards to the plan-selected
    pipeline (``fused`` → the kernel pipelines, else the reference one).
    Use ``core.policy.decode_attention(q, view, plan)`` instead."""
    from .policy import CacheView, _warn_deprecated

    _warn_deprecated(
        "retrieval.fier_attention_decode(..., use_kernels/fused/one_pass)",
        "policy.decode_attention(q, view, plan) with "
        "pipeline='reference'|'two_pass'|'one_pass'",
    )
    if fused:
        from repro.kernels import ops as kops

        view = CacheView.slab(K, V, qk, length)
        fn = kops.fier_decode_one_pass if one_pass else kops.fier_decode_two_pass
        return fn(
            q, view, budget, group_reduce=group_reduce, sink=sink, recent=recent
        )
    return fier_decode_reference(
        q, K, V, qk, budget, length,
        group_reduce=group_reduce, sink=sink, recent=recent,
        use_kernels=use_kernels,
    )
