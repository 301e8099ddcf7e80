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
directly from the slabs.  The gather keeps a whole budget block of row
copies in flight, and the next block's behind it (``_gather_rows``):
its time was one HBM round trip per two rows, not bytes.  Mosaic DMAs
whole (Hkv, D) tiles, so each copy brings every kv head of the row and
the kernel picks its head in VMEM (DESIGN.md §Chip layouts).

Both use the same online-softmax over budget blocks of blk_k rows
(``ops.ATTEND_BLK`` = 128: with all 16 heads of each row, the fused
kernels' two K + V buffers take 2 MiB at D = 128 bf16); larger budgets
carry (m, d) across blocks.

Grids: (B·Hkv, budget/blk_k) unfused; (B, Hkv, budget/blk_k) fused (the
fused kernel indexes the seq-major [B, S, Hkv, D] slabs directly, so the
batch and head coordinates stay separate).  Invalid slots (selection
padding when budget > length) arrive as an int8 mask.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fused_retrieval import check_vmem, head_rows

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

def _attend_scratch(blk_k, Hkv, D, k_dtype, v_dtype):
    """The gather's VMEM: two buffers of blk_k all-heads rows each for K
    and V (``_gather_rows``)."""
    return [((2, blk_k, Hkv, D), k_dtype), ((2, blk_k, Hkv, D), v_dtype)]


def _check_attend_vmem(blk_k, Hkv, D, k_dtype, v_dtype) -> None:
    """Trace-time guard: the gather's two buffers and the f32 copy of one
    buffer that ``head_rows`` makes for K and for V fit the scoped VMEM."""
    f32 = [((blk_k, Hkv, D), jnp.float32)] * 2
    check_vmem(
        f"select-and-attend at blk_k={blk_k}, Hkv={Hkv}, D={D}",
        _attend_scratch(blk_k, Hkv, D, k_dtype, v_dtype) + f32,
    )


# rows a gather loop iteration issues, unrolled: the scalar core pays the
# loop's own overhead once per 8 rows (69 → 57 ns a row pair on a v5e)
_ROWS_PER_ITER = 8


def _for_each_row(n: int, body) -> None:
    """``body(i)`` for i in [0, n), in order, gcd(n, ``_ROWS_PER_ITER``)
    rows unrolled an iteration."""
    unroll = math.gcd(n, _ROWS_PER_ITER)

    def step(g, carry):
        for r in range(unroll):
            body(g * unroll + r)
        return carry

    jax.lax.fori_loop(0, n // unroll, step, 0)


def _gather_rows(row_src, idx_ref, k_vmem, v_vmem, sems):
    """Bring this grid step's budget block of selected rows into VMEM, with
    the next block's row copies started behind it; returns the buffer the
    block is in.  Shared by the slab and the paged kernel.

    row_src(t) → (K row, V row): logical token t's HBM refs, each
    (1, Hkv, D) — how a row is addressed is all the kernels differ in.
    idx_ref [1, budget] int32 (SMEM): the (batch, kv head)'s whole index
    row.  k_vmem/v_vmem [2, blk_k, Hkv, D]: budget block j lands in buffer
    j % 2, its i-th selected row in row i.  sems [2, 2] DMA: (buffer) ×
    (K, V).  Every K row copy of a buffer signals one semaphore (V
    likewise) and is waited for by a descriptor of its own size, so a
    whole block's copies are in flight at once: the gather pays for
    issuing two descriptors a row, not for one HBM round trip per two
    rows.
    The first block of a (batch, kv head) is started by its own grid
    step; every step but the last starts block j + 1's copies before it
    waits for block j, so they land during block j's wait and attention.
    A wait counts its destination's bytes only, so every wait names one
    fixed source row and no index is read twice.
    """
    j = pl.program_id(2)
    blk_k = k_vmem.shape[1]

    def start_block(blk):
        buf = jax.lax.rem(blk, 2)

        def start_row(i):
            k_src, v_src = row_src(idx_ref[0, blk * blk_k + i])
            pltpu.make_async_copy(
                k_src, k_vmem.at[buf, pl.ds(i, 1)], sems.at[buf, 0]
            ).start()
            pltpu.make_async_copy(
                v_src, v_vmem.at[buf, pl.ds(i, 1)], sems.at[buf, 1]
            ).start()

        _for_each_row(blk_k, start_row)

    @pl.when(j == 0)
    def _first():
        start_block(0)

    @pl.when(j + 1 < pl.num_programs(2))
    def _next():
        start_block(j + 1)

    buf = jax.lax.rem(j, 2)
    k_any, v_any = row_src(0)

    def wait_row(i):
        pltpu.make_async_copy(
            k_any, k_vmem.at[buf, pl.ds(i, 1)], sems.at[buf, 0]
        ).wait()
        pltpu.make_async_copy(
            v_any, v_vmem.at[buf, pl.ds(i, 1)], sems.at[buf, 1]
        ).wait()

    _for_each_row(blk_k, wait_row)
    return buf


def _attend_block(row_src, idx_ref, q_ref, mask_ref, out_ref, m_ref, d_ref,
                  k_vmem, v_vmem, sems, *, scale):
    """One (batch, kv-head, budget-block) grid step of select-and-attend:
    gather the block's rows (``_gather_rows``), then one online-softmax
    update over kv head ``h``'s rows of them."""
    h = pl.program_id(1)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        d_ref[...] = jnp.zeros_like(d_ref)
        out_ref[...] = jnp.zeros_like(out_ref)

    buf = _gather_rows(row_src, idx_ref, k_vmem, v_vmem, sems)
    _softmax_accumulate(
        q_ref[...].astype(jnp.float32), head_rows(k_vmem[buf], h),
        head_rows(v_vmem[buf], h), mask_ref[...].astype(jnp.int32) > 0,
        out_ref, m_ref, d_ref, scale=scale,
    )


def _fused_kernel(
    idx_ref, q_ref, mask_ref, k_hbm, v_hbm, out_ref, m_ref, d_ref,
    k_vmem, v_vmem, sems, *, scale,
):
    """One (batch, kv-head, budget-block) step of fused select-and-attend.

    idx_ref [1, budget] int32 (SMEM); q [rep, D]; mask int8 [1, blk_k];
    k_hbm/v_hbm: *whole* seq-major cache slabs [B, S, Hkv, D] (ANY space —
    never staged through VMEM wholesale); token t of batch b is row
    ``[b, t]``.  k_vmem/v_vmem [2, blk_k, Hkv, D] and sems [2, 2]: the
    gather's buffers (``_gather_rows``) — every kv head of each selected
    row, since Mosaic DMAs whole (Hkv, D) tiles — from which
    ``head_rows`` picks head ``h``.
    """
    b = pl.program_id(0)

    def row_src(t):
        return k_hbm.at[b, pl.ds(t, 1)], v_hbm.at[b, pl.ds(t, 1)]

    _attend_block(row_src, idx_ref, q_ref, mask_ref, out_ref, m_ref, d_ref,
                  k_vmem, v_vmem, sems, scale=scale)


def _attend_call(kernel, q, K, V, idx, mask, *, blk_k, interpret,
                 lead_specs=(), lead_args=()):
    """The ``pallas_call`` both fused attend kernels share: grid (B, Hkv,
    budget/blk_k); the kernel's own leading SMEM operands, then the index
    row, q, the mask and the two cache operands (ANY space)."""
    B, Hkv, rep, D = q.shape
    budget = idx.shape[2]
    assert budget % blk_k == 0
    _check_attend_vmem(blk_k, Hkv, D, K.dtype, V.dtype)
    scale = 1.0 / (D**0.5)
    out, m, d = pl.pallas_call(
        functools.partial(kernel, scale=scale),
        grid=(B, Hkv, budget // blk_k),
        in_specs=[
            *lead_specs,
            # the (batch, kv head)'s whole index row, one SMEM block at
            # every j: the step of block j reads block j + 1's indices too
            pl.BlockSpec(
                (None, None, 1, budget), lambda b, h, j: (b, h, 0, 0),
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
            *(pltpu.VMEM(sh, dt) for sh, dt in
              _attend_scratch(blk_k, Hkv, D, K.dtype, V.dtype)),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        interpret=interpret,
    )(*lead_args, idx[:, :, None, :], q, mask, K, V)
    den = jnp.maximum(d[..., 0], 1e-30)
    return out / den[..., None]


@functools.partial(jax.jit, static_argnames=("blk_k", "interpret"))
def fused_sparse_attention_hm(
    q: jax.Array,
    K: jax.Array,
    V: jax.Array,
    idx: jax.Array,
    mask: jax.Array,
    *,
    blk_k: int = 128,
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
    return _attend_call(
        _fused_kernel, q, K, V, idx, mask,
        blk_k=min(blk_k, idx.shape[2]), interpret=interpret,
    )


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
    ``bt_ref [1, n_btab]``.  The gather and the online-softmax epilogue
    are shared, so paged and slab outputs are bit-identical at equal
    ``blk_k``.
    """

    def row_src(t):
        phys = bt_ref[0, t // block_size]
        off = jax.lax.rem(t, block_size)
        return k_hbm.at[phys, pl.ds(off, 1)], v_hbm.at[phys, pl.ds(off, 1)]

    _attend_block(row_src, idx_ref, q_ref, mask_ref, out_ref, m_ref, d_ref,
                  k_vmem, v_vmem, sems, scale=scale)


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
    blk_k: int = 128,
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
    assert k_pool.shape[1] == block_size, (k_pool.shape, block_size)
    n_btab = block_table.shape[1]
    return _attend_call(
        functools.partial(_paged_fused_kernel, block_size=block_size),
        q, k_pool, v_pool, idx, mask,
        blk_k=min(blk_k, idx.shape[2]), interpret=interpret,
        lead_specs=[pl.BlockSpec(
            (None, 1, n_btab), lambda b, h, j: (b, 0, 0),
            memory_space=pltpu.SMEM,
        )],
        lead_args=[block_table[:, None]],
    )
