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
    (``topk_select``'s trick: float order == unsigned order) and stored
    in a VMEM key row, block i in row i — the one sweep over HBM;
  * τ, the budget-th largest key, is found exactly by 32 counting passes
    over the VMEM row (one bit of τ each, from the top), and m = the
    strictly-greater count by one more;
  * a compaction pass over the VMEM row, a lane-width of tokens a step,
    places the selected indices { key > τ } ∪ first (budget − m) ties in
    ascending position order — the same index *set* ``lax.top_k`` returns
    on the same scores.

Per-token state in HBM: none.  The score tensors simply never exist as
arrays — each block's scores live in VREGs until its keys are stored in
VMEM.  The only outputs are the index set ``[BH, budget]`` and the
(lane-padded) τ/m scalars.

Cost: one streaming sweep over the packed codes, 1/16 of the bf16 key
bytes (Eq. 8) plus the scale/zero side-car — far below the 2·4·Hq·S
score-tensor round trip the fusion removes (at Hq = 32, D = 128: score
round trip ≈ 256·S bytes vs codes = 16·S bytes per batch row).  The τ
search and the compaction read VMEM only.

VMEM per program: 2 double-buffer slots of the (codes + scale + zero)
block; the key row, S·4 B when the block is a multiple of 128 tokens
(slab, blk_s = 512) and S/bs·512 B otherwise (paged, rows padded to 128
lanes: 192 KiB at S = 12288, bs = 32; 512 KiB at S = 32768); and the
[1, budget] rank scratch, 32 KiB at budget 1024.  A trace-time check
holds the sum under the scoped VMEM limit.  Grid: (B·Hkv,) for the
slab, (B, Hkv) for the paged kernel.

Mosaic notes (CPU CI interprets the exact kernel code): counts are
reductions, in-block prefix counts a 0/1 matmul, the rank placement
masked reductions over iota comparisons in a 128-aligned window — no
sort, cumsum, reshape or scatter (DESIGN.md §Chip layouts).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.retrieval import NEG_INF

from .fier_score import score_block
from .topk_select import LANE, _sortable_keys, _unsortable, kth_largest_key

VMEM_LIMIT_BYTES = 16 * 2**20   # Mosaic's default scoped VMEM limit on a v5e


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


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


def _threshold_select(keys_ref, out_v, budget: int, idx_ref, tau_ref, m_ref):
    """Exact τ search + tie-aware index compaction over the VMEM key row.

    keys_ref [nb, n] uint32 holds the monotone keys of the masked kv
    scores, block i's n keys in row i: position i·n + o sits at [i, o].
    out_v [1, budget rounded up to LANE] int32 is scratch.  Shared
    verbatim by the contiguous (slab) and the page-table-aware retrieval
    kernels: both write their one sweep's keys here; only the
    *addressing* of the code stream differs.  Writes the selected index
    set, τ, and the strictly-greater count to the (lane-padded) output
    refs.

    Written for Mosaic: no sort, cumsum, reshape or scatter.  Counts are
    reductions over the row, in-block prefix counts a 0/1 matmul (exact:
    bf16 ones, f32 sums), and the rank placement masked reductions over
    iota comparisons.
    """
    nb, n = keys_ref.shape

    def count(pred):
        return jnp.sum(pred(keys_ref[...]).astype(jnp.int32))

    # ---- phase 1: τ, the budget-th largest key, and m = |{ key > τ }|
    tau = kth_largest_key(lambda c: count(lambda k: k >= c), budget)
    m = count(lambda k: k > tau)

    # ---- phase 2: compact { key > τ } at ranks [0, m) and the first
    # (budget − m) ties at [m, budget), each in ascending position order.
    # A step takes `tile` whole blocks, at most a lane-width of tokens, so
    # one class's ranks in a step lie in one `win`-lane window of out_v.
    tile = math.gcd(nb, max(1, LANE // n))
    wpad = out_v.shape[1]
    win = min(wpad, -(-(tile * n) // LANE) * LANE + LANE)
    upto = (_iota((n, n), 1) <= _iota((n, n), 0)).astype(jnp.bfloat16)
    before = _iota((tile, tile), 0) < _iota((tile, tile), 1)   # [r', r]
    out_v[...] = jnp.zeros(out_v.shape, jnp.int32)

    def ranks(sel_rows, sel_cols):
        """Inclusive count of selected tokens up to each token of the step,
        in position order ([n, tile], block r in column r), and the total."""
        tot = jnp.sum(sel_rows.astype(jnp.int32), axis=1, keepdims=True)
        off = jnp.sum(jnp.where(before, tot, 0), axis=0, keepdims=True)
        incl = jnp.dot(
            upto, sel_cols.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)
        return incl + off, jnp.sum(tot)

    def place(first, sel, rank, total, pos):
        """out[first + rank − 1] = pos for the selected tokens of a step."""
        @pl.when(total > 0)
        def _():
            w = pl.multiple_of(jnp.minimum(first // LANE * LANE, wpad - win), LANE)
            dest = jnp.where(sel, first - w + rank - 1, -1)          # [n, tile]
            acc = out_v[:, pl.ds(w, win)]
            for r in range(tile):
                acc = acc + jnp.sum(
                    jnp.where(dest[:, r:r + 1] == _iota((n, win), 1),
                              pos[:, r:r + 1], 0),
                    axis=0, keepdims=True,
                )
            out_v[:, pl.ds(w, win)] = acc

    def compact_step(j, carry):
        ngt, ntie = carry
        rows = keys_ref[pl.ds(pl.multiple_of(j * tile, tile), tile), :]
        cols = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(rows, jnp.int32).T, jnp.uint32
        )                                                   # [n, tile]
        pos = (j * tile + _iota((n, tile), 1)) * n + _iota((n, tile), 0)
        gt, tie = cols > tau, cols == tau
        cgt, gt_total = ranks(rows > tau, gt)
        ctie, tie_total = ranks(rows == tau, tie)
        place(ngt, gt, cgt, gt_total, pos)
        place(m + ntie, tie & (ntie + ctie <= budget - m), ctie, tie_total, pos)
        return ngt + gt_total, ntie + tie_total

    jax.lax.fori_loop(0, nb // tile, compact_step, (jnp.int32(0), jnp.int32(0)))
    idx_ref[...] = out_v[:, :budget].reshape(idx_ref.shape)
    tau_ref[...] = _unsortable(jnp.broadcast_to(tau, tau_ref.shape))
    m_ref[...] = jnp.broadcast_to(m, m_ref.shape)


def _masked_block_keys(s, i, blk_s, length, sink, recent, group_reduce):
    """Group-reduce + mask one scored block and lift to monotone keys.

    s [rep, blk_s] f32 (VREG-resident scores) → keys uint32 [1, blk_s].
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
    return _sortable_keys(kv)


def _sweep_keys(keys_ref, start_block, wait_block, block_keys):
    """The one HBM sweep: block i is waited for, scored, and its keys
    stored in row i of the VMEM key row, with block i + 1's copies in
    flight meanwhile.  Scores exist only in VREGs, one block at a time."""
    nb = keys_ref.shape[0]
    start_block(0)

    def body(i, carry):
        @pl.when(i + 1 < nb)
        def _prefetch():
            start_block(i + 1)

        wait_block(i)
        keys_ref[pl.ds(i, 1), :] = block_keys(i)
        return carry

    jax.lax.fori_loop(0, nb, body, 0)


def _padded_bytes(shape, dtype) -> int:
    """VMEM bytes of a buffer: its two minor dims padded to Mosaic's
    (sublane, 128)-tile of the dtype (8 rows at 32 bits, 16 at 16, 32 at 8)."""
    itemsize = jnp.dtype(dtype).itemsize
    *major, rows, lanes = shape
    sub = 8 * 4 // itemsize
    rows, lanes = -(-rows // sub) * sub, -(-lanes // LANE) * LANE
    return math.prod(major) * rows * lanes * itemsize


def check_vmem(what: str, scratch) -> None:
    """Trace-time guard: a kernel's VMEM buffers, ``[(shape, dtype)]``,
    must fit the scoped VMEM limit; ``what`` names the kernel and the
    shape that sized them."""
    need = sum(_padded_bytes(sh, dt) for sh, dt in scratch)
    if need > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"{what}: its VMEM scratch takes {need} B, "
            f"over the {VMEM_LIMIT_BYTES} B scoped limit"
        )


def _kernel(
    len_ref, q_ref, codes_hbm, scale_hbm, zero_hbm,
    idx_ref, tau_ref, m_ref,
    codes_v, scale_v, zero_v, keys_v, out_v, sems, *,
    budget: int, group: int, blk_s: int, group_reduce: str,
    sink: int, recent: int,
):
    """One (batch·kv-head) row of one-pass retrieval.

    len_ref [1, 1] int32 (SMEM); q_ref [rep, D]; codes/scale/zero: whole
    head-major slabs [BH, S/8|S/g, D] in ANY space (DMA'd blockwise);
    idx_ref [1, budget] int32; tau_ref [1, LANE] f32; m_ref [1, LANE]
    int32; codes_v/scale_v/zero_v: [2, ...] double-buffer scratch;
    keys_v [S/blk_s, blk_s] uint32, the row's keys; out_v the
    compaction's rank scratch; sems [2, 3] DMA semaphores (slot × operand).
    """
    b = pl.program_id(0)
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
        """Monotone-uint32 keys [1, blk_s] of block i's masked kv scores."""
        slot = jax.lax.rem(i, 2)
        s = score_block(
            qbf, codes_v[slot], scale_v[slot], zero_v[slot], group=group
        )                                                   # [rep, blk_s]
        return _masked_block_keys(s, i, blk_s, length, sink, recent, group_reduce)

    _sweep_keys(keys_v, start_block, wait_block, block_keys)
    _threshold_select(keys_v, out_v, budget, idx_ref, tau_ref, m_ref)


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
    scratch = [
        ((2, blk // 8, D), jnp.uint8),
        ((2, blk // group, D), scale.dtype),
        ((2, blk // group, D), zero.dtype),
        ((S // blk, blk), jnp.uint32),
        ((1, -(-budget // LANE) * LANE), jnp.int32),
    ]
    check_vmem(f"one-pass retrieval at S={S}", scratch)
    idx, tau, m = pl.pallas_call(
        functools.partial(
            _kernel, budget=budget, group=group, blk_s=blk,
            group_reduce=group_reduce, sink=sink, recent=recent,
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
            *(pltpu.VMEM(sh, dt) for sh, dt in scratch),
            pltpu.SemaphoreType.DMA((2, 3)),
        ],
        interpret=interpret,
    )(lengths[:, None, None], q, codes, scale, zero)
    return idx[:, 0], tau[:, 0, 0], m[:, 0, 0]


# ------------------------------------------------------- page-table variant

def _paged_kernel(
    bt_ref, len_ref, q_ref, codes_hbm, scale_hbm, zero_hbm,
    idx_ref, tau_ref, m_ref,
    codes_v, scale_v, zero_v, keys_v, out_v, sems, *,
    budget: int, group: int, block_size: int, group_reduce: str,
    sink: int, recent: int,
):
    """One (batch, kv-head) row of one-pass retrieval over a *paged* pool.

    bt_ref [1, n_btab] int32 (SMEM) — this request's block table row;
    len_ref [1, 1] int32 (SMEM); q_ref [rep, D]; codes/scale/zero: whole
    paged side-car pools [N, bs/8|bs/g, Hkv, D] in ANY space; outputs as
    in the contiguous kernel; the DMA scratch holds every kv head of a
    block (``head_rows`` picks this row's head); keys_v [n_btab, bs]
    uint32 holds the row's keys, out_v the compaction's ranks.  The per-row DMA stream walks
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

    _sweep_keys(keys_v, start_block, wait_block, block_keys)
    _threshold_select(keys_v, out_v, budget, idx_ref, tau_ref, m_ref)


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
    scratch = [
        ((2, block_size // 8, Hkv, D), jnp.uint8),
        ((2, block_size // group, Hkv, D), scale.dtype),
        ((2, block_size // group, Hkv, D), zero.dtype),
        ((n_btab, block_size), jnp.uint32),
        ((1, -(-budget // LANE) * LANE), jnp.int32),
    ]
    check_vmem(f"one-pass retrieval at S={S}", scratch)
    idx, tau, m = pl.pallas_call(
        functools.partial(
            _paged_kernel, budget=budget, group=group, block_size=block_size,
            group_reduce=group_reduce, sink=sink, recent=recent,
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
            *(pltpu.VMEM(sh, dt) for sh, dt in scratch),
            pltpu.SemaphoreType.DMA((2, 3)),
        ],
        interpret=interpret,
    )(block_table[:, None], lengths[:, None, None], q, codes, scale, zero)
    return idx[:, :, 0], tau[:, :, 0, 0], m[:, :, 0, 0]
