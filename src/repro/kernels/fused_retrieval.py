"""Pallas TPU kernel: one-pass fused retrieval — the decode selection stage
with per-token scores that never touch HBM.

The two-pass pipeline (PR 1) still materialises the f32 approximate-score
tensors in HBM between its kernels: ``fier_score`` writes ``[B, Hq, S]``
(4·Hq·S bytes), XLA reads it back for the GQA group-reduce and writes
``[B, Hkv, S]``, and ``topk_select`` reads that again.  At S = 128k this
round trip (≥ 2·4·Hq·S bytes per layer per step) rivals the packed-code
read itself — the same recall-side traffic FreeKV (arXiv 2505.13109)
identifies as the dominant retrieval cost at scale.

This kernel fuses the whole retrieval stage into one ``pallas_call``:

  * the packed 1-bit codes (and the bf16 group scale/zero side-car) are
    bound with ``memory_space=ANY`` and streamed HBM→VMEM block-by-block
    with double-buffered async DMA (the next block's three copies are in
    flight while the current block is scored);
  * each block is scored in VREGs with the *exact* expression of the
    score-scan kernel (``fier_score.score_block`` — bit-identical f32
    scores), group-reduced over the query group (``max``/``sum``) and
    masked (``length``/``sink``/``recent``) in-register;
  * the masked block scores are reinterpreted as monotone uint32 keys
    (``topk_select``'s trick: float order == unsigned order) and drive an
    exact radix-histogram search for τ, the budget-th largest key —
    ``NPASS`` = 4 sweeps over the code blocks, each accumulating a
    256-bucket histogram of the next 8 key bits among the keys matching
    the prefix found so far;
  * a final sweep re-scores the blocks and compacts the selected indices
    { key > τ } ∪ first (budget − m) ties in ascending position order —
    the same index *set* ``lax.top_k`` returns on the same scores.

Per-token state in HBM: none.  The score tensors simply never exist as
arrays — each block's scores live in VREGs for the duration of one fold
step.  The only outputs are the index set ``[BH, budget]`` and the
(lane-padded) τ/m scalars.

Cost: NPASS + 1 = 5 streaming sweeps over the packed codes.  The codes
are 1/16 of the bf16 key bytes (Eq. 8), so five sweeps ≈ 0.31× the key
bytes — still far below the 2·4·Hq·S score-tensor round trip the fusion
removes (at Hq = 32, D = 128: score round trip ≈ 256·S bytes vs
5·codes = 80·S bytes per batch row, and the gap widens with Hq).

VMEM per step: 2 double-buffer slots of (codes + scale + zero) block ≈
2·(blk_s·D/8 + 2·(blk_s/g)·D·2) bytes — 48 KiB at blk_s = 512, D = 128,
g = 32 — plus the [1, budget] index block.  Grid: (B·Hkv,).

Mosaic notes (CPU CI interprets the exact kernel code): the histogram,
the in-block prefix counts and the rank placement of the compaction are
masked reductions over iota comparisons — no sort, cumsum or scatter,
none of which Mosaic lowers (DESIGN.md §Chip layouts).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.retrieval import NEG_INF

from .fier_score import score_block
from .topk_select import LANE, _sortable_keys, _unsortable

NPASS = 4    # radix-histogram passes: 8 bits of the uint32 keys per pass
RADIX = 256  # buckets per pass


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _to_col(x: jax.Array) -> jax.Array:
    """Exact [1, n] → [n, 1] int32 transpose as a masked lane reduction
    (each output sums one value and n − 1 zeros).  Mosaic has no general
    small-shape transpose; this is n² VPU work, trivial at block size."""
    n = x.shape[1]
    return jnp.sum(
        jnp.where(_iota((n, n), 0) == _iota((n, n), 1), x, 0),
        axis=1, keepdims=True,
    )


def head_rows(x: jax.Array, h) -> jax.Array:
    """Rows of kv head ``h`` out of an all-heads tile: [r, Hkv, D] → [r, D]
    (int32 for integer input, else f32), exact — each output sums one value
    and Hkv − 1 zeros.

    The paged pools keep Hkv as the second-minor (tiled) dim, and Mosaic
    DMAs only whole tiles: a single head's rows cannot be sliced out of
    HBM, so the kernels DMA every head of a row and pick ``h`` in VMEM."""
    x = x.astype(jnp.int32 if jnp.issubdtype(x.dtype, jnp.integer) else jnp.float32)
    sel = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) == h
    return jnp.sum(jnp.where(sel, x, 0), axis=1)


def _threshold_select(sweep, budget: int, idx_ref, tau_ref, m_ref):
    """Radix-histogram τ search + tie-aware index compaction over a
    ``sweep(fold, init)`` abstraction that folds over scored blocks
    ``(keys_row [1, n], keys_col [n, 1], pos_col [n, 1])``.

    Shared verbatim by the contiguous (slab) and the page-table-aware
    retrieval kernels: both produce per-block monotone-uint32 keys of the
    masked kv scores; only the *addressing* of the code stream differs.
    Writes the selected index set, τ, and the strictly-greater count to
    the (lane-padded) output refs.

    Written for Mosaic: no cumsum, reversal or scatter.  Prefix counts
    and the rank scatter are masked reductions over iota comparisons, and
    the loop carries are [1, 1] vectors.
    """
    # ---- phase 1: radix-histogram search for τ (the budget-th largest key)
    def radix_pass(p, carry):
        t, remaining, greater = carry                       # [1, 1] each
        pw = p.astype(jnp.uint32)
        shift = jnp.uint32(24) - jnp.uint32(8) * pw
        # participation: keys matching the 8p prefix bits found so far
        # (p = 0: everyone; the clamp keeps the dead branch's shift < 32)
        himask = jnp.where(
            p == 0,
            jnp.uint32(0),
            jnp.uint32(0xFFFFFFFF)
            << jnp.minimum(jnp.uint32(32) - jnp.uint32(8) * pw, jnp.uint32(31)),
        )

        def fold(blk, hist):
            _, keys, _ = blk                                # keys [n, 1]
            n = keys.shape[0]
            part = (keys & himask) == t
            digit = ((keys >> shift) & jnp.uint32(0xFF)).astype(jnp.int32)
            onehot = (digit == _iota((n, RADIX), 1)) & part  # [n, RADIX]
            return hist + jnp.sum(
                onehot.astype(jnp.int32), axis=0, keepdims=True
            )

        hist = sweep(fold, jnp.zeros((1, RADIX), jnp.int32))  # [1, RADIX]
        # ge[j] = count(digit ≥ j), bucket j on sublanes
        ge = jnp.sum(
            jnp.where(
                _iota((RADIX, RADIX), 1) >= _iota((RADIX, RADIX), 0), hist, 0
            ),
            axis=1, keepdims=True,
        )                                                   # [RADIX, 1]
        # τ's digit: the highest bucket where the ≥-count reaches `remaining`
        jstar = jnp.max(
            jnp.where(ge >= remaining, _iota((RADIX, 1), 0), -1),
            axis=0, keepdims=True,
        )                                                   # [1, 1]
        above = jnp.sum(
            jnp.where(_iota((1, RADIX), 1) > jstar, hist, 0),
            axis=1, keepdims=True,
        )
        t = t | (jstar.astype(jnp.uint32) << shift)
        return t, remaining - above, greater + above

    tau_key, _, m = jax.lax.fori_loop(
        0, NPASS, radix_pass,
        (
            jnp.zeros((1, 1), jnp.uint32),
            jnp.full((1, 1), budget, jnp.int32),
            jnp.zeros((1, 1), jnp.int32),
        ),
    )
    # m = |{ key > τ }| exactly: every strictly-greater key is counted at
    # the first radix pass where its digit exceeds τ's (it matches the
    # prefix up to that pass), and never again after it stops matching.

    # ---- phase 2: re-score and compact { key > τ } ∪ first (budget−m) ties
    def compact_fold(blk, carry):
        ngt, ntie, out = carry
        keys_row, keys_col, pos_col = blk
        n = keys_row.shape[1]
        gt_row = (keys_row > tau_key).astype(jnp.int32)     # [1, n]
        tie_row = (keys_row == tau_key).astype(jnp.int32)
        gt = keys_col > tau_key                             # [n, 1]
        tie = keys_col == tau_key
        # inclusive in-block prefix counts: row i sums tokens t ≤ i
        upto = _iota((n, n), 1) <= _iota((n, n), 0)
        cgt = jnp.sum(jnp.where(upto, gt_row, 0), axis=1, keepdims=True)
        ctie = jnp.sum(jnp.where(upto, tie_row, 0), axis=1, keepdims=True)
        take_tie = tie & (ntie + ctie <= budget - m)
        # rank of each selected token: >τ fill [0, m) in ascending
        # position, taken ties fill [m, budget); −1 = not selected
        dest = jnp.where(
            gt, ngt + cgt - 1, jnp.where(take_tie, m + ntie + ctie - 1, -1)
        )                                                   # [n, 1]
        out = out + jnp.sum(
            jnp.where(dest == _iota((n, budget), 1), pos_col, 0),
            axis=0, keepdims=True,
        )                                                   # [1, budget]
        return (
            ngt + jnp.sum(gt_row, axis=1, keepdims=True),
            ntie + jnp.sum(tie_row, axis=1, keepdims=True),
            out,
        )

    _, _, out = sweep(
        compact_fold,
        (
            jnp.zeros((1, 1), jnp.int32),
            jnp.zeros((1, 1), jnp.int32),
            jnp.zeros((1, budget), jnp.int32),
        ),
    )
    idx_ref[...] = out.reshape(idx_ref.shape)
    tau_ref[...] = jnp.broadcast_to(_unsortable(tau_key), tau_ref.shape)
    m_ref[...] = jnp.broadcast_to(m, m_ref.shape)


def _masked_block_keys(s, i, blk_s, length, sink, recent, group_reduce):
    """Group-reduce + mask one scored block and lift to monotone keys.

    s [rep, blk_s] f32 (VREG-resident scores) → (keys uint32 [1, blk_s],
    the same keys as a column [blk_s, 1], positions int32 [blk_s, 1]).
    Shared by the slab and paged kernels so the masking arithmetic is
    identical bit for bit.
    """
    if group_reduce == "max":
        kv = s.max(axis=0, keepdims=True)                   # [1, blk_s]
    else:
        kv = s.sum(axis=0, keepdims=True)
    pos = i * blk_s + _iota((1, blk_s), 1)
    kv = jnp.where(pos < length, kv, NEG_INF)
    if sink > 0:
        kv = jnp.where(pos < sink, jnp.inf, kv)
    if recent > 0:
        is_recent = (pos >= length - recent) & (pos < length)
        kv = jnp.where(is_recent, jnp.inf, kv)
    keys = _sortable_keys(kv)
    keys_col = jax.lax.bitcast_convert_type(
        _to_col(jax.lax.bitcast_convert_type(keys, jnp.int32)), jnp.uint32
    )
    return keys, keys_col, i * blk_s + _iota((blk_s, 1), 0)


def _kernel(
    len_ref, q_ref, codes_hbm, scale_hbm, zero_hbm,
    idx_ref, tau_ref, m_ref,
    codes_v, scale_v, zero_v, sems, *,
    budget: int, group: int, blk_s: int, group_reduce: str,
    sink: int, recent: int, S: int,
):
    """One (batch·kv-head) row of one-pass retrieval.

    len_ref [1, 1] int32 (SMEM); q_ref [rep, D]; codes/scale/zero: whole
    head-major slabs [BH, S/8|S/g, D] in ANY space (DMA'd blockwise);
    idx_ref [1, budget] int32; tau_ref [1, LANE] f32; m_ref [1, LANE]
    int32; codes_v/scale_v/zero_v: [2, ...] double-buffer scratch;
    sems [2, 3] DMA semaphores (slot × operand).
    """
    b = pl.program_id(0)
    nb = S // blk_s
    n8 = blk_s // 8
    ng = blk_s // group
    length = len_ref[0, 0]
    qbf = q_ref[...].astype(jnp.bfloat16)

    def block_copies(i, slot):
        """The three HBM→VMEM copy descriptors for code block i."""
        return (
            pltpu.make_async_copy(
                codes_hbm.at[b, pl.ds(i * n8, n8), :],
                codes_v.at[slot], sems.at[slot, 0],
            ),
            pltpu.make_async_copy(
                scale_hbm.at[b, pl.ds(i * ng, ng), :],
                scale_v.at[slot], sems.at[slot, 1],
            ),
            pltpu.make_async_copy(
                zero_hbm.at[b, pl.ds(i * ng, ng), :],
                zero_v.at[slot], sems.at[slot, 2],
            ),
        )

    def start_block(i):
        for cp in block_copies(i, jax.lax.rem(i, 2)):
            cp.start()

    def wait_block(i):
        for cp in block_copies(i, jax.lax.rem(i, 2)):
            cp.wait()

    def block_keys(i):
        """Monotone-uint32 keys of block i's masked kv scores (row and
        column) and their positions.

        Scores exist only here, in VREGs, for the duration of one fold.
        """
        slot = jax.lax.rem(i, 2)
        s = score_block(
            qbf, codes_v[slot], scale_v[slot], zero_v[slot], group=group
        )                                                   # [rep, blk_s]
        return _masked_block_keys(s, i, blk_s, length, sink, recent, group_reduce)

    def sweep(fold, init):
        """fold(keys, pos, carry) over all code blocks, next block's DMA
        in flight while the current block is scored."""
        start_block(0)

        def body(i, carry):
            @pl.when(i + 1 < nb)
            def _prefetch():
                start_block(i + 1)

            wait_block(i)
            return fold(block_keys(i), carry)

        return jax.lax.fori_loop(0, nb, body, init)

    _threshold_select(sweep, budget, idx_ref, tau_ref, m_ref)


@functools.partial(
    jax.jit,
    static_argnames=(
        "budget", "group", "blk_s", "group_reduce", "sink", "recent",
        "interpret",
    ),
)
def fused_retrieve_hm(
    q: jax.Array,
    codes: jax.Array,
    scale: jax.Array,
    zero: jax.Array,
    lengths: jax.Array,
    budget: int,
    *,
    group: int,
    blk_s: int = 512,
    group_reduce: str = "max",
    sink: int = 0,
    recent: int = 0,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Head-major one-pass retrieval.

    q [BH, rep, D]; codes [BH, S/8, D] uint8; scale/zero [BH, S/g, D];
    lengths [BH] int32 → (idx int32 [BH, budget], tau f32 [BH],
    m int32 [BH]).  The index *set* equals ``lax.top_k`` over the masked,
    group-reduced ``fier_score`` scores; tau is the budget-th largest
    masked score and m the strictly-greater count.
    """
    BH, rep, D = q.shape
    S = codes.shape[1] * 8
    assert 0 < budget <= S, (budget, S)
    if group_reduce not in ("max", "sum"):
        raise ValueError(f"unknown group reduction {group_reduce!r}")
    blk = min(blk_s, S)
    while S % blk:
        blk //= 2
    assert blk % 8 == 0 and blk % group == 0, (blk, group)
    idx, tau, m = pl.pallas_call(
        functools.partial(
            _kernel, budget=budget, group=group, blk_s=blk,
            group_reduce=group_reduce, sink=sink, recent=recent, S=S,
        ),
        grid=(BH,),
        in_specs=[
            pl.BlockSpec(
                (None, 1, 1), lambda b: (b, 0, 0), memory_space=pltpu.SMEM
            ),
            pl.BlockSpec((None, rep, D), lambda b: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, budget), lambda b: (b, 0, 0)),
            pl.BlockSpec((None, 1, LANE), lambda b: (b, 0, 0)),
            pl.BlockSpec((None, 1, LANE), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, 1, budget), jnp.int32),
            jax.ShapeDtypeStruct((BH, 1, LANE), jnp.float32),
            jax.ShapeDtypeStruct((BH, 1, LANE), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, blk // 8, D), jnp.uint8),
            pltpu.VMEM((2, blk // group, D), scale.dtype),
            pltpu.VMEM((2, blk // group, D), zero.dtype),
            pltpu.SemaphoreType.DMA((2, 3)),
        ],
        interpret=interpret,
    )(lengths[:, None, None], q, codes, scale, zero)
    return idx[:, 0], tau[:, 0, 0], m[:, 0, 0]


# ------------------------------------------------------- page-table variant

def _paged_kernel(
    bt_ref, len_ref, q_ref, codes_hbm, scale_hbm, zero_hbm,
    idx_ref, tau_ref, m_ref,
    codes_v, scale_v, zero_v, sems, *,
    budget: int, group: int, block_size: int, group_reduce: str,
    sink: int, recent: int, n_btab: int,
):
    """One (batch, kv-head) row of one-pass retrieval over a *paged* pool.

    bt_ref [1, n_btab] int32 (SMEM) — this request's block table row;
    len_ref [1, 1] int32 (SMEM); q_ref [rep, D]; codes/scale/zero: whole
    paged side-car pools [N, bs/8|bs/g, Hkv, D] in ANY space; outputs as
    in the contiguous kernel; scratch holds every kv head of a block
    (``head_rows`` picks this row's head).  The per-row DMA stream walks
    ``block_table[b]`` instead of a contiguous slab: logical code block
    ``i`` is fetched from pool row ``bt[i]`` (unallocated entries point
    at the null block, whose garbage scores are masked by ``length``).
    The scoring block size *is* the cache block size, so the selected
    indices are logical token positions ``i·bs + offset`` — τ search and
    compaction are shared verbatim with the slab kernel.
    """
    h = pl.program_id(1)
    bs = block_size
    n8 = bs // 8
    ng = bs // group
    length = len_ref[0, 0]
    qbf = q_ref[...].astype(jnp.bfloat16)

    def block_copies(i, slot):
        phys = bt_ref[0, i]
        return (
            pltpu.make_async_copy(
                codes_hbm.at[phys], codes_v.at[slot], sems.at[slot, 0]
            ),
            pltpu.make_async_copy(
                scale_hbm.at[phys], scale_v.at[slot], sems.at[slot, 1]
            ),
            pltpu.make_async_copy(
                zero_hbm.at[phys], zero_v.at[slot], sems.at[slot, 2]
            ),
        )

    def start_block(i):
        for cp in block_copies(i, jax.lax.rem(i, 2)):
            cp.start()

    def wait_block(i):
        for cp in block_copies(i, jax.lax.rem(i, 2)):
            cp.wait()

    def block_keys(i):
        slot = jax.lax.rem(i, 2)
        s = score_block(
            qbf, head_rows(codes_v[slot], h), head_rows(scale_v[slot], h),
            head_rows(zero_v[slot], h), group=group,
        )                                                   # [rep, bs]
        return _masked_block_keys(s, i, bs, length, sink, recent, group_reduce)

    def sweep(fold, init):
        start_block(0)

        def body(i, carry):
            @pl.when(i + 1 < n_btab)
            def _prefetch():
                start_block(i + 1)

            wait_block(i)
            return fold(block_keys(i), carry)

        return jax.lax.fori_loop(0, n_btab, body, init)

    _threshold_select(sweep, budget, idx_ref, tau_ref, m_ref)


@functools.partial(
    jax.jit,
    static_argnames=(
        "budget", "group", "block_size", "group_reduce", "sink", "recent",
        "interpret",
    ),
)
def paged_fused_retrieve_hm(
    q: jax.Array,
    codes: jax.Array,
    scale: jax.Array,
    zero: jax.Array,
    block_table: jax.Array,
    lengths: jax.Array,
    budget: int,
    *,
    group: int,
    block_size: int,
    group_reduce: str = "max",
    sink: int = 0,
    recent: int = 0,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Page-table-aware one-pass retrieval.

    q [B, Hkv, rep, D]; codes [N, bs/8, Hkv, D] uint8; scale/zero
    [N, bs/g, Hkv, D]; block_table [B, n_btab] int32; lengths [B] int32 →
    (idx int32 [B, Hkv, budget], tau f32 [B, Hkv], m int32 [B, Hkv]).

    Returns the exact index set / τ / m of ``fused_retrieve_hm`` on the
    logical (table-gathered) cache contents: scores are computed by the
    same ``score_block`` at per-token granularity, so values — hence keys,
    τ, and the compacted index order — are bit-identical to the slab
    kernel's.  Per-token score state in HBM: none, as in the slab kernel.
    """
    B, Hkv, rep, D = q.shape
    n_btab = block_table.shape[1]
    S = n_btab * block_size
    assert 0 < budget <= S, (budget, S)
    assert codes.shape[1] * 8 == block_size, (codes.shape, block_size)
    if group_reduce not in ("max", "sum"):
        raise ValueError(f"unknown group reduction {group_reduce!r}")
    idx, tau, m = pl.pallas_call(
        functools.partial(
            _paged_kernel, budget=budget, group=group, block_size=block_size,
            group_reduce=group_reduce, sink=sink, recent=recent, n_btab=n_btab,
        ),
        grid=(B, Hkv),
        in_specs=[
            pl.BlockSpec(
                (None, 1, n_btab), lambda b, h: (b, 0, 0),
                memory_space=pltpu.SMEM,
            ),
            pl.BlockSpec(
                (None, 1, 1), lambda b, h: (b, 0, 0), memory_space=pltpu.SMEM
            ),
            pl.BlockSpec((None, None, rep, D), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((None, None, 1, budget), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((None, None, 1, LANE), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((None, None, 1, LANE), lambda b, h: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, 1, budget), jnp.int32),
            jax.ShapeDtypeStruct((B, Hkv, 1, LANE), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, 1, LANE), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, block_size // 8, Hkv, D), jnp.uint8),
            pltpu.VMEM((2, block_size // group, Hkv, D), scale.dtype),
            pltpu.VMEM((2, block_size // group, Hkv, D), zero.dtype),
            pltpu.SemaphoreType.DMA((2, 3)),
        ],
        interpret=interpret,
    )(block_table[:, None], lengths[:, None, None], q, codes, scale, zero)
    return idx[:, :, 0], tau[:, :, 0, 0], m[:, :, 0, 0]
