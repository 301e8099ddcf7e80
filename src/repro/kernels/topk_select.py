"""Pallas TPU kernel: blockwise top-k *threshold* select on approximate scores.

The unfused decode path ran ``jax.lax.top_k`` over the full f32 score row —
a global sort (O(S log S), and on TPU a multi-pass XLA sort that round-trips
HBM).  Selection only needs the *k-th largest value* though: once τ (the
budget-th score) is known, the top-k index set is exactly

    { i : s_i > τ }  ∪  first (budget − m) indices with s_i == τ,

where m = |{ i : s_i > τ }| — the same set ``lax.top_k`` returns (it breaks
ties toward lower indices, and so does taking τ-ties in ascending index
order).  This file finds τ with a radix binary search over the *bit
patterns* of the scores — 32 blockwise counting passes over VMEM-resident
keys, no sort, exact result — and compacts the indices with O(S)
cumsum + scatter (``compact_indices``), not a sort.

Monotone key trick: reinterpret f32 as uint32 and flip (sign ? all : top)
bits; then float order == unsigned integer order.  −0.0 is canonicalised to
+0.0 first so float equality and key equality agree on ties.

Grid: (BH,).  VMEM per step ≈ 2·S·4 bytes (scores f32 + keys u32) — 256 KiB
at S=32k, 4 MiB at S=512k; beyond that shard the sequence (the distributed
path selects per shard anyway).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128  # lane-padded scalar outputs, matching sparse_attention's carries


def _canon(s: jax.Array) -> jax.Array:
    """Collapse -0.0 → +0.0 so key order and float ties agree."""
    return jnp.where(s == 0.0, 0.0, s)


def _sortable_keys(s: jax.Array) -> jax.Array:
    """f32 → uint32 such that float order == unsigned order."""
    u = jax.lax.bitcast_convert_type(_canon(s), jnp.uint32)
    return jnp.where(u >> 31 == 0, u | jnp.uint32(0x80000000), ~u)


def _unsortable(key: jax.Array) -> jax.Array:
    u = jnp.where(key >> 31 == 1, key ^ jnp.uint32(0x80000000), ~key)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def kth_largest_key(count_ge, budget: int) -> jax.Array:
    """The ``budget``-th largest key, given ``count_ge(c)`` = |{ key ≥ c }|:
    the largest t with count_ge(t) ≥ budget, found one bit at a time from
    the top (32 counting passes).  Shared with the one-pass kernels."""
    def bit_step(i, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        return jnp.where(count_ge(cand) >= budget, cand, t)

    return jax.lax.fori_loop(0, 32, bit_step, jnp.uint32(0))


def _kernel(s_ref, tau_ref, m_ref, keys_ref, *, budget: int, blk_s: int):
    """One (batch·kv-head) row: radix binary search for the budget-th key.

    s_ref [1, S] f32; tau_ref [1, LANE] f32; m_ref [1, LANE] int32;
    keys_ref [1, S] uint32 scratch.
    """
    S = s_ref.shape[1]
    nb = S // blk_s
    keys_ref[...] = _sortable_keys(s_ref[...])

    def count_ge(cand):
        """|{ key >= cand }| — blockwise scan over the VMEM-resident keys."""
        def blk(i, acc):
            k = keys_ref[:, pl.ds(i * blk_s, blk_s)]
            return acc + jnp.sum((k >= cand).astype(jnp.int32))

        return jax.lax.fori_loop(0, nb, blk, jnp.int32(0))

    t = kth_largest_key(count_ge, budget)
    # t is the largest key with count(>= t) >= budget ⇒ exactly the
    # budget-th largest key;  m = strictly-greater count = count(>= t+1).
    m = count_ge(t + jnp.uint32(1))
    tau_ref[...] = jnp.full(tau_ref.shape, _unsortable(t), jnp.float32)
    m_ref[...] = jnp.full(m_ref.shape, m, jnp.int32)


@functools.partial(jax.jit, static_argnames=("budget", "blk_s", "interpret"))
def topk_threshold_hm(
    scores: jax.Array,
    budget: int,
    *,
    blk_s: int = 2048,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Head-major threshold search.

    scores f32 [BH, S] → (tau f32 [BH], m int32 [BH]) where tau is the
    ``budget``-th largest score per row and m the strictly-greater count.
    """
    BH, S = scores.shape
    assert 0 < budget <= S, (budget, S)
    blk_s = min(blk_s, S)
    while S % blk_s:
        blk_s //= 2
    tau, m = pl.pallas_call(
        functools.partial(_kernel, budget=budget, blk_s=blk_s),
        grid=(BH,),
        in_specs=[pl.BlockSpec((1, S), lambda b: (b, 0))],
        out_specs=[
            pl.BlockSpec((1, LANE), lambda b: (b, 0)),
            pl.BlockSpec((1, LANE), lambda b: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, LANE), jnp.float32),
            jax.ShapeDtypeStruct((BH, LANE), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((1, S), jnp.uint32)],
        interpret=interpret,
    )(scores.astype(jnp.float32))
    return tau[:, 0], m[:, 0]


def compact_indices(
    scores: jax.Array, tau: jax.Array, m: jax.Array, budget: int
) -> jax.Array:
    """O(S) sort-free compaction: scores [BH, S], tau/m [BH] → idx [BH, budget].

    Destination of each selected element is its rank: strictly-greater
    elements land at their running count − 1 (ascending index order), the
    first (budget − m) τ-ties fill the tail.  One cumsum + one bounded
    scatter — never a sort.  The returned index *set* equals
    ``lax.top_k``'s (both break ties toward lower indices); the order is
    ascending-by-position within each class, which downstream attention is
    invariant to.
    """
    BH, S = scores.shape
    s = _canon(scores.astype(jnp.float32))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (BH, S))
    gt = s > tau[:, None]
    tie = s == tau[:, None]
    cgt = jnp.cumsum(gt, axis=-1).astype(jnp.int32)
    ctie = jnp.cumsum(tie, axis=-1).astype(jnp.int32)
    take_tie = tie & (ctie <= (budget - m)[:, None])
    dest = jnp.where(
        gt, cgt - 1, jnp.where(take_tie, m[:, None] + ctie - 1, budget)
    )
    rows = jnp.arange(BH, dtype=jnp.int32)[:, None]
    out = jnp.zeros((BH, budget + 1), jnp.int32)  # col `budget` = discard pad
    out = out.at[rows, dest].set(pos, mode="drop")
    return out[:, :budget]
