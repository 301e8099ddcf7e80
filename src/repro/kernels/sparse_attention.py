"""Pallas TPU kernels: exact decode attention over the FIER-selected tokens.

Two variants:

``sparse_attention_hm`` (unfused) — consumes pre-gathered K'/V' (budget
rows).  The XLA gather that feeds it *materialises* 2·budget·D bytes per
kv head per layer per step in HBM, written once and read once — the
dominant retrieval cost at serving scale (FreeKV observes the same on
GPU).  Kept as the fallback and as the shape the jnp oracle mirrors.

``fused_sparse_attention_hm`` (fused select-and-attend) — consumes top-k
*indices* (int32) plus the full seq-major cache slabs, and pulls each
selected row HBM→VMEM with per-row async DMA inside the kernel.  No K'/V'
copy ever exists in HBM: the only cache traffic is budget rows *read*
directly from the slabs.  The gather loop double-issues the K and V row
copies so both are in flight per step.  Mosaic DMAs whole (Hkv, D) tiles,
so each copy brings every kv head of the row and the kernel picks its
head in VMEM (DESIGN.md §Chip layouts).

Both use the same online-softmax over budget blocks of blk_k rows
(``ops.ATTEND_BLK`` = 128: with all 16 heads of each row, K + V scratch
is 1 MiB at D = 128 bf16); larger budgets carry (m, d) across blocks.

Grids: (B·Hkv, budget/blk_k) unfused; (B, Hkv, budget/blk_k) fused (the
fused kernel indexes the seq-major [B, S, Hkv, D] slabs directly, so the
batch and head coordinates stay separate).  Invalid slots (selection
padding when budget > length) arrive as an int8 mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fused_retrieval import head_rows

NEG_INF = -1e30


def _softmax_accumulate(q, k, v, valid, out_ref, m_ref, d_ref, *, scale):
    """One online-softmax block update, shared by every attend kernel.

    q [rep, D] f32; k/v [blk_k, D]; valid bool [1, blk_k]; out [rep, D]
    f32; m/d [rep, 128] f32 carries (lane-padded scalars).  Keeping this
    expression shared is what makes the slab, fused, and paged attend
    paths bit-identical at equal ``blk_k``.
    """
    s = jax.lax.dot_general(
        q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                            # [rep, blk_k]
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[..., 0]                               # [rep]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(valid, p, 0.0)
    out_ref[...] = out_ref[...] * alpha[:, None] + jax.lax.dot(
        p, v.astype(jnp.float32), preferred_element_type=jnp.float32
    )
    d_ref[..., 0] = d_ref[..., 0] * alpha + p.sum(axis=-1)
    m_ref[..., 0] = m_new


def _kernel(q_ref, k_ref, v_ref, mask_ref, out_ref, m_ref, d_ref, *, scale):
    """Online-softmax step over one budget block.

    q [rep, D]; k/v [blk_k, D]; mask int8 [1, blk_k]; out [rep, D] f32;
    m/d [rep, 128] f32 carries (lane-padded scalars).
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        d_ref[...] = jnp.zeros_like(d_ref)
        out_ref[...] = jnp.zeros_like(out_ref)

    _softmax_accumulate(
        q_ref[...].astype(jnp.float32), k_ref[...], v_ref[...],
        mask_ref[...].astype(jnp.int32) > 0, out_ref, m_ref, d_ref,
        scale=scale,
    )


@functools.partial(jax.jit, static_argnames=("blk_k", "interpret"))
def sparse_attention_hm(
    q: jax.Array,
    k_sel: jax.Array,
    v_sel: jax.Array,
    mask: jax.Array,
    *,
    blk_k: int = 1024,
    interpret: bool = True,
) -> jax.Array:
    """Head-major sparse decode attention.

    q [BH, rep, D]; k_sel/v_sel [BH, budget, D]; mask int8 [BH, 1, budget]
    → out f32 [BH, rep, D].
    """
    BH, rep, D = q.shape
    budget = k_sel.shape[1]
    blk_k = min(blk_k, budget)
    assert budget % blk_k == 0
    grid = (BH, budget // blk_k)
    scale = 1.0 / (D**0.5)
    out, m, d = pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, rep, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, blk_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, blk_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, 1, blk_k), lambda b, j: (b, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((None, rep, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, rep, 128), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, rep, 128), lambda b, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, rep, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, rep, 128), jnp.float32),
            jax.ShapeDtypeStruct((BH, rep, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k_sel, v_sel, mask)
    den = jnp.maximum(d[..., 0], 1e-30)
    return out / den[..., None]


# ------------------------------------------------------ fused select+attend

def _idx_blocks(idx: jax.Array, blk_k: int) -> jax.Array:
    """[B, Hkv, budget] → [B, Hkv, budget/blk_k, 1, blk_k]: one grid step's
    indices are a whole trailing (1, blk_k) tile, the block shape Mosaic
    accepts for an SMEM operand."""
    B, Hkv, budget = idx.shape
    return idx.reshape(B, Hkv, budget // blk_k, 1, blk_k)


def _fused_kernel(
    idx_ref, q_ref, mask_ref, k_hbm, v_hbm, out_ref, m_ref, d_ref,
    k_vmem, v_vmem, sems, *, scale,
):
    """One (batch, kv-head, budget-block) step of fused select-and-attend.

    idx_ref [1, blk_k] int32 (SMEM); q [rep, D]; mask int8 [1, blk_k];
    k_hbm/v_hbm: *whole* seq-major cache slabs [B, S, Hkv, D] (ANY space —
    never staged through VMEM wholesale); k_vmem/v_vmem [blk_k, Hkv, D]
    scratch — every kv head of each selected row, since Mosaic DMAs whole
    (Hkv, D) tiles — from which ``head_rows`` picks head ``h``;
    sems: [2, 2] DMA semaphores — (slot = row parity) × (K, V) — so the
    gather loop keeps the next row's copies in flight while waiting on
    the current row's (double-buffered, not serial round-trips).
    """
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        d_ref[...] = jnp.zeros_like(d_ref)
        out_ref[...] = jnp.zeros_like(out_ref)

    blk_k = k_vmem.shape[0]

    def row_copies(i):
        """The (K, V) row-i DMA descriptors; slot = i mod 2 double-buffers
        the semaphores so row i+1's copies are in flight while row i's
        are being waited on."""
        row = idx_ref[0, i]
        slot = jax.lax.rem(i, 2)
        kcp = pltpu.make_async_copy(
            k_hbm.at[b, pl.ds(row, 1)], k_vmem.at[pl.ds(i, 1)],
            sems.at[slot, 0],
        )
        vcp = pltpu.make_async_copy(
            v_hbm.at[b, pl.ds(row, 1)], v_vmem.at[pl.ds(i, 1)],
            sems.at[slot, 1],
        )
        return kcp, vcp

    def start_row(i):
        kcp, vcp = row_copies(i)
        kcp.start()
        vcp.start()

    start_row(0)

    def gather(i, _):
        @pl.when(i + 1 < blk_k)
        def _prefetch():
            start_row(i + 1)

        kcp, vcp = row_copies(i)
        kcp.wait()
        vcp.wait()
        return 0

    jax.lax.fori_loop(0, blk_k, gather, 0)

    _softmax_accumulate(
        q_ref[...].astype(jnp.float32), head_rows(k_vmem[...], h),
        head_rows(v_vmem[...], h), mask_ref[...].astype(jnp.int32) > 0,
        out_ref, m_ref, d_ref, scale=scale,
    )


@functools.partial(jax.jit, static_argnames=("blk_k", "interpret"))
def fused_sparse_attention_hm(
    q: jax.Array,
    K: jax.Array,
    V: jax.Array,
    idx: jax.Array,
    mask: jax.Array,
    *,
    blk_k: int = 1024,
    interpret: bool = True,
) -> jax.Array:
    """Fused select-and-attend decode attention.

    q [B, Hkv, rep, D]; K/V seq-major slabs [B, S, Hkv, D]; idx int32
    [B, Hkv, budget]; mask int8 [B, Hkv, 1, budget] → out f32
    [B, Hkv, rep, D].

    The slabs are bound with ``memory_space=ANY`` — the kernel DMAs only
    the ``budget`` selected rows, so per step per kv head the cache
    traffic is budget·D·2 bytes *read* for K (same for V) and zero bytes
    written, vs. the unfused path's additional budget·D·2 written + read
    back for each materialised K'/V' copy.
    """
    B, Hkv, rep, D = q.shape
    budget = idx.shape[2]
    blk_k = min(blk_k, budget)
    assert budget % blk_k == 0
    grid = (B, Hkv, budget // blk_k)
    scale = 1.0 / (D**0.5)
    out, m, d = pl.pallas_call(
        functools.partial(_fused_kernel, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (None, None, None, 1, blk_k), lambda b, h, j: (b, h, j, 0, 0),
                memory_space=pltpu.SMEM,
            ),
            pl.BlockSpec((None, None, rep, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((None, None, 1, blk_k), lambda b, h, j: (b, h, 0, j)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((None, None, rep, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((None, None, rep, 128), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((None, None, rep, 128), lambda b, h, j: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, rep, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, rep, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, rep, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_k, Hkv, D), K.dtype),
            pltpu.VMEM((blk_k, Hkv, D), V.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        interpret=interpret,
    )(_idx_blocks(idx, blk_k), q, mask, K, V)
    den = jnp.maximum(d[..., 0], 1e-30)
    return out / den[..., None]


# ------------------------------------------------- paged select+attend

def _paged_fused_kernel(
    bt_ref, idx_ref, q_ref, mask_ref, k_hbm, v_hbm, out_ref, m_ref, d_ref,
    k_vmem, v_vmem, sems, *, scale, block_size,
):
    """One (batch, kv-head, budget-block) step of *paged* select-and-attend.

    Identical to ``_fused_kernel`` except for row addressing: the cache
    operands are the block-pool slabs [N, bs, Hkv, D] (ANY space) and the
    selected *logical* token index ``t`` is translated in-kernel to
    ``(block_table[t // bs], t % bs)`` via the SMEM-resident table row
    ``bt_ref [1, n_btab]``.  The online-softmax epilogue is shared, so paged
    and slab outputs are bit-identical at equal ``blk_k``.
    """
    h = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        d_ref[...] = jnp.zeros_like(d_ref)
        out_ref[...] = jnp.zeros_like(out_ref)

    blk_k = k_vmem.shape[0]

    def row_copies(i):
        row = idx_ref[0, i]
        phys = bt_ref[0, row // block_size]
        off = jax.lax.rem(row, block_size)
        slot = jax.lax.rem(i, 2)
        kcp = pltpu.make_async_copy(
            k_hbm.at[phys, pl.ds(off, 1)], k_vmem.at[pl.ds(i, 1)],
            sems.at[slot, 0],
        )
        vcp = pltpu.make_async_copy(
            v_hbm.at[phys, pl.ds(off, 1)], v_vmem.at[pl.ds(i, 1)],
            sems.at[slot, 1],
        )
        return kcp, vcp

    def start_row(i):
        kcp, vcp = row_copies(i)
        kcp.start()
        vcp.start()

    start_row(0)

    def gather(i, _):
        @pl.when(i + 1 < blk_k)
        def _prefetch():
            start_row(i + 1)

        kcp, vcp = row_copies(i)
        kcp.wait()
        vcp.wait()
        return 0

    jax.lax.fori_loop(0, blk_k, gather, 0)

    _softmax_accumulate(
        q_ref[...].astype(jnp.float32), head_rows(k_vmem[...], h),
        head_rows(v_vmem[...], h), mask_ref[...].astype(jnp.int32) > 0,
        out_ref, m_ref, d_ref, scale=scale,
    )


@functools.partial(jax.jit, static_argnames=("block_size", "blk_k", "interpret"))
def paged_fused_sparse_attention_hm(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_table: jax.Array,
    idx: jax.Array,
    mask: jax.Array,
    *,
    block_size: int,
    blk_k: int = 1024,
    interpret: bool = True,
) -> jax.Array:
    """Paged fused select-and-attend decode attention.

    q [B, Hkv, rep, D]; k_pool/v_pool block-pool slabs [N, bs, Hkv, D];
    block_table int32 [B, n_btab]; idx int32 [B, Hkv, budget] (*logical*
    token positions); mask int8 [B, Hkv, 1, budget] → out f32
    [B, Hkv, rep, D].  As in the contiguous fused kernel, only the
    ``budget`` selected rows move HBM→VMEM — no K'/V' copy, and no
    materialised logical-slab view of the pool either.
    """
    B, Hkv, rep, D = q.shape
    budget = idx.shape[2]
    blk_k = min(blk_k, budget)
    assert budget % blk_k == 0
    assert k_pool.shape[1] == block_size, (k_pool.shape, block_size)
    grid = (B, Hkv, budget // blk_k)
    scale = 1.0 / (D**0.5)
    n_btab = block_table.shape[1]
    out, m, d = pl.pallas_call(
        functools.partial(_paged_fused_kernel, scale=scale, block_size=block_size),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (None, 1, n_btab), lambda b, h, j: (b, 0, 0),
                memory_space=pltpu.SMEM,
            ),
            pl.BlockSpec(
                (None, None, None, 1, blk_k), lambda b, h, j: (b, h, j, 0, 0),
                memory_space=pltpu.SMEM,
            ),
            pl.BlockSpec((None, None, rep, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((None, None, 1, blk_k), lambda b, h, j: (b, h, 0, j)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((None, None, rep, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((None, None, rep, 128), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((None, None, rep, 128), lambda b, h, j: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, rep, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, rep, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, rep, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_k, Hkv, D), k_pool.dtype),
            pltpu.VMEM((blk_k, Hkv, D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        interpret=interpret,
    )(block_table[:, None], _idx_blocks(idx, blk_k), q, mask, k_pool, v_pool)
    den = jnp.maximum(d[..., 0], 1e-30)
    return out / den[..., None]
