"""Paged KV cache subsystem: allocator invariants, page-table-aware kernel
exactness vs the slab path, prefix sharing / copy-on-write / preemption
through the serving stack."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core import quantize as qz
from repro.core.policy import CacheView, PolicyConfig
from repro.kernels import ops, ref
from repro.kvcache import cache as kvcache
from repro.kvcache import paged
from repro.models import build_model
from repro.serving import ContinuousScheduler, Engine, Request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))
from flopcount import count_fn_flops, count_fn_score_bytes  # noqa: E402

# (B, S, Hkv, Hq, D, g, bs): the GQA matrix of test_kernels, with a cache
# block size dividing S (bs % 8 == 0, bs % g == 0)
PAGED_SHAPES = [
    (2, 256, 2, 4, 64, 32, 32),
    (1, 512, 1, 8, 128, 32, 64),
    (2, 128, 4, 4, 32, 16, 16),
    (1, 1024, 2, 2, 128, 64, 128),
    (3, 192, 3, 6, 16, 8, 24),
]


# ------------------------------------------------------------- allocator

def test_block_allocator_invariants():
    a = paged.BlockAllocator(6, 16)
    assert a.usable == 5 and a.n_free == 5 and a.n_in_use == 0
    got = [a.alloc() for _ in range(5)]
    assert sorted(got) == [1, 2, 3, 4, 5]  # null block 0 never handed out
    assert a.alloc() is None and a.n_in_use == 5
    for b in got:
        a.free(b)
    assert a.n_in_use == 0 and a.n_free == 5
    with pytest.raises(AssertionError):
        a.free(got[0])  # double free


def test_block_allocator_refcounts_and_prefix_cache():
    a = paged.BlockAllocator(4, 8)
    b = a.alloc()
    a.register(b, 42)
    assert a.lookup(42) == b and a.ref[b] == 2  # shared
    a.free(b)
    assert a.ref[b] == 1 and a.n_in_use == 1
    a.free(b)
    # parked free-cached: still hittable, still counted free
    assert a.ref[b] == 0 and a.n_free == 3
    assert a.lookup(42) == b and a.ref[b] == 1
    a.free(b)
    # eviction: exhausting the plain free list reclaims the cached block
    got = [a.alloc() for _ in range(3)]
    assert None not in got and a.lookup(42) is None


def test_block_allocator_peek_and_blocks_needed():
    a = paged.BlockAllocator(8, 8)
    keys = paged.block_hash_chain(list(range(20)), 8)  # 3 blocks
    assert a.blocks_needed(20, keys) == 3
    bids = [a.alloc() for _ in range(3)]
    for bid, key in zip(bids, keys):
        a.register(bid, key)
    assert a.peek(keys) == (3, 0)
    assert a.blocks_needed(20, keys) == 0
    # an extended prompt shares the 2 full blocks, misses the tail
    keys2 = paged.block_hash_chain(list(range(16)) + [99] * 4, 8)
    assert a.peek(keys2) == (2, 0) and a.blocks_needed(20, keys2) == 1
    for bid in bids:
        a.free(bid)
    # all three parked free-cached: hits now charge revivals
    assert a.peek(keys) == (3, 3) and a.blocks_needed(20, keys) == 3


def test_block_hash_chain_prefix_property():
    k1 = paged.block_hash_chain([1, 2, 3, 4, 5, 6], 4)
    k2 = paged.block_hash_chain([1, 2, 3, 4, 9, 9], 4)
    k3 = paged.block_hash_chain([7, 2, 3, 4, 5, 6], 4)
    assert k1[0] == k2[0] and k1[1] != k2[1]   # shared full block, split tail
    assert k1[0] != k3[0] and k1[1] != k3[1]   # chained: early split propagates


# ------------------------------------------------------------- validation

def test_init_layer_cache_validates_divisibility():
    fier = PolicyConfig(kind="fier", group=32)
    with pytest.raises(ValueError, match="divisible by 8"):
        kvcache.init_layer_cache(1, 1, 60, 2, 8, fier)
    with pytest.raises(ValueError, match="divisible by group"):
        kvcache.init_layer_cache(1, 1, 72, 2, 8, fier)
    quest = PolicyConfig(kind="quest", page=16)
    with pytest.raises(ValueError, match="quest page"):
        kvcache.init_layer_cache(1, 1, 72, 2, 8, quest)
    kvcache.init_layer_cache(1, 1, 64, 2, 8, fier)  # divisible: fine


def test_init_paged_pool_validates_block_size():
    fier = PolicyConfig(kind="fier", group=32)
    with pytest.raises(ValueError, match="divisible by 8"):
        paged.init_paged_pool(1, 4, 12, 2, 8, fier)
    with pytest.raises(ValueError, match="divisible by group"):
        paged.init_paged_pool(1, 4, 16, 2, 8, fier)
    with pytest.raises(ValueError, match="null block"):
        paged.init_paged_pool(1, 1, 32, 2, 8, fier)
    pool = paged.init_paged_pool(2, 4, 32, 2, 8, fier)
    assert pool["meta"].codes.shape == (2, 4, 4, 2, 8)


# ----------------------------------------------- kernels: paged vs slab

def _slab_to_pool(arr, perm, N):
    """Chunk a slab leaf [B, S, ...] into pool blocks at a permuted layout."""
    B, S = arr.shape[:2]
    nb = perm.shape[1]
    pb = S // nb
    pool = jnp.zeros((N, pb, *arr.shape[2:]), arr.dtype)
    blocks = arr.reshape(B, nb, pb, *arr.shape[2:])
    return pool.at[perm.reshape(-1)].set(blocks.reshape(B * nb, pb, *arr.shape[2:]))


def _paged_inputs(B, S, Hkv, Hq, D, g, bs, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    K = jax.random.normal(k1, (B, S, Hkv, D), jnp.bfloat16)
    V = jax.random.normal(k2, (B, S, Hkv, D), jnp.bfloat16)
    q = jax.random.normal(k3, (B, Hq, D))
    qk = qz.quantize(K.astype(jnp.float32), g)
    nb = S // bs
    N = B * nb + 1
    rng = np.random.default_rng(seed)
    table = jnp.asarray(1 + rng.permutation(B * nb).reshape(B, nb), jnp.int32)
    k_pool, v_pool = _slab_to_pool(K, table, N), _slab_to_pool(V, table, N)
    meta = qz.QuantizedKeys(
        _slab_to_pool(qk.codes, table, N),
        _slab_to_pool(qk.scale, table, N),
        _slab_to_pool(qk.zero, table, N),
        g,
    )
    return q, K, V, qk, k_pool, v_pool, meta, table


@pytest.mark.parametrize("B,S,Hkv,Hq,D,g,bs", PAGED_SHAPES)
def test_paged_retrieve_exact_vs_slab(B, S, Hkv, Hq, D, g, bs):
    """Page-table-aware one-pass retrieval must return the *identical*
    index array as the slab kernel on the same logical cache contents
    (scores are bit-identical, both compact ascending-by-position)."""
    q, K, V, qk, k_pool, v_pool, meta, table = _paged_inputs(B, S, Hkv, Hq, D, g, bs)
    length = jnp.full((B,), S - 7, jnp.int32)
    for budget, sink, recent in [(min(64, S), 0, 0), (min(32, S), 4, 8)]:
        slab = ops.retrieve(
            q, CacheView.slab(None, None, qk, length), budget,
            sink=sink, recent=recent,
        )
        pview = CacheView.paged(None, None, meta, table, length)
        got = ops.retrieve(q, pview, budget, sink=sink, recent=recent)
        np.testing.assert_array_equal(np.asarray(slab), np.asarray(got))
        want = ref.retrieve(q, pview, budget, sink=sink, recent=recent)
        np.testing.assert_array_equal(
            np.sort(np.asarray(got), -1), np.sort(np.asarray(want), -1)
        )


@pytest.mark.parametrize("B,S,Hkv,Hq,D,g,bs,lengths,budget", [
    (3, 256, 2, 4, 64, 32, 32, (37, 200, 129), 64),
    (2, 192, 3, 6, 16, 8, 24, (5, 100), 120),
    (1, 512, 1, 8, 128, 32, 64, (300,), 400),
])
def test_paged_retrieve_ragged_tail_vs_slab(B, S, Hkv, Hq, D, g, bs, lengths, budget):
    """Per-slot lengths that end mid-block, table entries past each length
    on the null block, and budget > length for some slots: the masked
    tail of the kernel's key row selects the slab kernel's exact indices
    (order included), τ and m."""
    q, K, V, qk, k_pool, v_pool, meta, table = _paged_inputs(
        B, S, Hkv, Hq, D, g, bs, seed=2
    )
    length = jnp.asarray(lengths, jnp.int32)
    used = -(-length // bs)
    table = jnp.where(jnp.arange(S // bs)[None] < used[:, None], table, 0)
    kw = dict(sink=4, recent=8, return_stats=True)
    slab = ops.retrieve(q, CacheView.slab(None, None, qk, length), budget, **kw)
    pview = CacheView.paged(None, None, meta, table, length)
    got = ops.retrieve(q, pview, budget, **kw)
    for want_part, got_part in zip(slab, got):
        np.testing.assert_array_equal(np.asarray(want_part), np.asarray(got_part))
    want = ref.retrieve(q, pview, budget, sink=4, recent=8)
    np.testing.assert_array_equal(
        np.sort(np.asarray(got[0]), -1), np.sort(np.asarray(want), -1)
    )


def test_paged_retrieve_vmem_guard_names_S():
    """A key row over the scoped VMEM limit is refused while tracing, with
    the context length in the message (2**22 tokens at block 32: 64 MiB
    of 128-lane rows)."""
    from repro.kernels import fused_retrieval as fr

    bs, n_btab = 32, 2**17
    sds = jax.ShapeDtypeStruct
    args = (
        sds((1, 1, 1, 128), jnp.float32),
        sds((2, bs // 8, 1, 128), jnp.uint8),
        sds((2, 1, 1, 128), jnp.bfloat16),
        sds((2, 1, 1, 128), jnp.bfloat16),
        sds((1, n_btab), jnp.int32),
        sds((1,), jnp.int32),
    )
    with pytest.raises(ValueError, match=f"S={bs * n_btab}"):
        jax.eval_shape(
            lambda *a: fr.paged_fused_retrieve_hm(*a, 64, group=32, block_size=bs),
            *args,
        )


@pytest.mark.parametrize("B,S,Hkv,Hq,D,g,bs", PAGED_SHAPES)
def test_paged_decode_bit_identical_vs_slab(B, S, Hkv, Hq, D, g, bs):
    """Paged one-pass decode (retrieval + select-and-attend, block table
    walked in-kernel) is bit-identical to the slab fused pipeline."""
    q, K, V, qk, k_pool, v_pool, meta, table = _paged_inputs(
        B, S, Hkv, Hq, D, g, bs, seed=1
    )
    length = jnp.full((B,), S - 5, jnp.int32)
    budget = min(64, S)
    slab = ops.fier_decode_one_pass(
        q, CacheView.slab(K, V, qk, length), budget
    )
    pview = CacheView.paged(k_pool, v_pool, meta, table, length)
    got = ops.fier_decode_one_pass(q, pview, budget)
    np.testing.assert_array_equal(np.asarray(slab), np.asarray(got))
    want = ref.paged_fused_fier_attention_decode(
        q, k_pool, v_pool, meta, table, budget, length
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2,
    )


# (B, S, Hkv, Hq, D, bs, budget, blk_k, lengths) for the select-and-attend
# gather: rep 1 at 16 KV heads and rep 16 at 4 (the two served groups);
# budgets of several blk_k blocks, so each block's row copies are started
# by the grid step before it; one single-block budget; slots whose budget
# exceeds their length (masked padding rows) and whose length ends inside
# a block (the ragged tail block), with table entries past it on the null
# block that the padding rows read; a blk_k of 12, which the gather's
# loops cannot take 8 rows an iteration
ATTEND_CASES = [
    (2, 256, 16, 16, 32, 32, 128, 32, (256, 77)),
    (2, 256, 4, 64, 32, 32, 96, 32, (250, 40)),
    (1, 128, 16, 16, 128, 16, 64, 64, (50,)),
    (3, 192, 3, 6, 16, 24, 64, 16, (37, 192, 100)),
    (2, 96, 2, 4, 16, 16, 36, 12, (90, 30)),
]


def _attend_rows(S, Hkv, budget, bs, lengths, seed):
    """Ascending distinct positions a (slot, kv head), as retrieval hands
    them over: every row of the slot's ragged tail block, some rows past
    its length where it has them, the rest drawn at random."""
    rng = np.random.default_rng(seed)
    idx = np.zeros((len(lengths), Hkv, budget), np.int32)
    for b, L in enumerate(lengths):
        tail = np.arange(L // bs * bs, L)
        past = np.arange(L, min(S, L + 5))
        for h in range(Hkv):
            rest = np.setdiff1d(np.arange(S), np.r_[tail, past])
            pick = rng.choice(rest, budget - tail.size - past.size, replace=False)
            idx[b, h] = np.sort(np.r_[tail, past, pick])
    return jnp.asarray(idx)


@pytest.mark.parametrize("B,S,Hkv,Hq,D,bs,budget,blk,lengths", ATTEND_CASES)
def test_paged_attend_bit_identical_vs_slab(B, S, Hkv, Hq, D, bs, budget, blk, lengths):
    """The paged and the slab select-and-attend kernels return the same bits
    on the same logical rows, and the blockwise oracle's: bit for bit at
    rep > 1; at rep 1 XLA's CPU backend rounds the one-row dots by their
    context, so there to 1e-6 (a wrong row or mask moves the output by
    far more).  The oracle itself matches a float64 softmax."""
    q, K, V, qk, k_pool, v_pool, meta, table = _paged_inputs(
        B, S, Hkv, Hq, D, 8, bs, seed=3
    )
    length = jnp.asarray(lengths, jnp.int32)
    used = -(-length // bs)
    table = jnp.where(jnp.arange(S // bs)[None] < used[:, None], table, 0)
    idx = _attend_rows(S, Hkv, budget, bs, lengths, seed=4)
    slab = ops.attend_selected(q, CacheView.slab(K, V, None, length), idx, blk_k=blk)
    pview = CacheView.paged(k_pool, v_pool, None, table, length)
    got = ops.attend_selected(q, pview, idx, blk_k=blk)
    np.testing.assert_array_equal(np.asarray(slab), np.asarray(got))

    want = np.asarray(ref.blockwise_sparse_attention(q, K, V, idx, length, blk))
    if Hq // Hkv > 1:
        np.testing.assert_array_equal(np.asarray(got), want)
    else:
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)

    rows = np.arange(B)[:, None, None]
    heads = np.arange(Hkv)[None, :, None]
    ksel = np.asarray(K, np.float64)[rows, np.asarray(idx), heads]   # [B, Hkv, budget, D]
    vsel = np.asarray(V, np.float64)[rows, np.asarray(idx), heads]
    q4 = np.asarray(q, np.float64).reshape(B, Hkv, Hq // Hkv, D)
    s = np.einsum("bhrd,bhkd->bhrk", q4, ksel) / np.sqrt(D)
    s = np.where((np.asarray(idx) < np.asarray(length)[:, None, None])[:, :, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    exact = np.einsum("bhrk,bhkd->bhrd", p / p.sum(-1, keepdims=True), vsel)
    np.testing.assert_allclose(want, exact.reshape(B, Hq, D), rtol=1e-5, atol=1e-5)


def test_paged_append_matches_slab_append():
    """Appending one token through the block table leaves the same logical
    cache (K/V rows and refreshed side-car) as the slab append."""
    B, S, H, D, g, bs = 2, 64, 2, 8, 8, 16
    q, K, V, qk, k_pool, v_pool, meta, table = _paged_inputs(B, S, H, 4, D, g, bs)
    cfg = PolicyConfig(kind="fier", group=g)
    length = jnp.array([17, 40], jnp.int32)
    kn = jax.random.normal(jax.random.PRNGKey(9), (B, 1, H, D), jnp.bfloat16)
    vn = jax.random.normal(jax.random.PRNGKey(10), (B, 1, H, D), jnp.bfloat16)

    K2, V2 = kvcache.append_kv(K, V, kn, vn, length)
    m2 = kvcache.append_token_metadata(qk, K2, length, cfg)

    kp2, vp2 = paged.paged_append_kv(k_pool, v_pool, kn, vn, table, length)
    mp2 = paged.paged_append_token_metadata(meta, kp2, table, length, cfg)

    np.testing.assert_array_equal(
        np.asarray(K2, np.float32),
        np.asarray(paged.gather_block_rows(kp2, table), np.float32),
    )
    np.testing.assert_array_equal(
        np.asarray(V2, np.float32),
        np.asarray(paged.gather_block_rows(vp2, table), np.float32),
    )
    np.testing.assert_array_equal(
        np.asarray(m2.codes), np.asarray(paged.gather_block_rows(mp2.codes, table))
    )
    np.testing.assert_array_equal(
        np.asarray(m2.scale, np.float32),
        np.asarray(paged.gather_block_rows(mp2.scale, table), np.float32),
    )


def test_paged_onepass_zero_score_bytes():
    """The paged one-pass decode keeps the per-token score tensors out of
    HBM, exactly like the slab one-pass kernel (the CI smoke gate)."""
    B, S, Hkv, Hq, D, g, bs = 1, 256, 2, 4, 32, 8, 32
    q, K, V, qk, k_pool, v_pool, meta, table = _paged_inputs(B, S, Hkv, Hq, D, g, bs)
    length = jnp.full((B,), S, jnp.int32)
    sb = count_fn_score_bytes(
        lambda q, kp, vp: ops.fier_decode_one_pass(
            q, CacheView.paged(kp, vp, meta, table, length), 32
        ),
        S, q, k_pool, v_pool,
    )
    assert sb == 0.0, sb


# --------------------------------------------------- serving integration

@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("olmo-1b")

    def mk(paged_mode, pool_blocks=0):
        pol = PolicyConfig(
            kind="fier", budget=16, group=8, skip_layers=1,
            pipeline="one_pass", layout="paged" if paged_mode else "slab",
            block_size=8, pool_blocks=pool_blocks,
        )
        return build_model(cfg, pol)

    slab = mk(False)
    params = slab.init(jax.random.PRNGKey(0))
    return cfg, mk, slab, params


def _reqs(n=4, max_new=5):
    return [
        Request(rid=i, tokens=list(range(3 + i, 11 + i)), max_new=max_new)
        for i in range(n)
    ]


def test_paged_scheduler_matches_slab(setup):
    """Same workload through a paged and a slab engine: identical outputs
    (the paged decode is bit-identical on the same logical contents)."""
    cfg, mk, slab, params = setup
    out_slab = ContinuousScheduler(
        Engine(slab, n_slots=3, capacity=64), params, pad_prompt_to=16
    ).run(_reqs())
    eng = Engine(mk(True), n_slots=3, capacity=64)
    out_paged = ContinuousScheduler(eng, params, pad_prompt_to=16).run(_reqs())
    assert out_slab == out_paged
    # every block came back: nothing resident after the run
    assert eng.allocator.n_in_use == 0


def test_paged_engine_decode_logits_match_slab(setup):
    """Direct engine-level check: insert + decode produce bit-identical
    logits slab-vs-paged on fresh caches."""
    cfg, mk, slab, params = setup
    toks = jnp.asarray(np.arange(1, 12, dtype=np.int32)[None])
    outs = []
    for bundle in (slab, mk(True)):
        eng = Engine(bundle, n_slots=2, capacity=64)
        cache = eng.new_cache()
        logits, cache = eng.insert(params, cache, toks, 11, slot=1)
        seq = [np.asarray(logits)]
        tok = jnp.asarray([0, int(jnp.argmax(logits[0]))], jnp.int32)
        active = jnp.asarray([False, True])
        for _ in range(3):
            if eng.paged:
                ok, cache = eng.advance_slot(cache, 1)
                assert ok
            tok_next, lg, cache = eng.decode(params, tok, cache, active=active)
            seq.append(np.asarray(lg[1]))
            tok = jnp.asarray([0, int(tok_next[1])], jnp.int32)
        outs.append(seq)
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)


def test_a_boundary_crossing_dispatches_nothing_before_the_decode_step(setup):
    """advance_slot on a block boundary only books the fresh tail block:
    the decode step itself scrubs it and enters it in the slot's table
    row, so a crossing step dispatches no program of its own."""
    cfg, mk, slab, params = setup
    eng = Engine(mk(True), n_slots=2, capacity=64)
    cache = eng.new_cache()
    toks = jnp.asarray(np.arange(1, 17, dtype=np.int32)[None])    # two whole blocks
    logits, cache = eng.insert(params, cache, toks, 16, slot=1)
    ok, after = eng.advance_slot(cache, 1)
    assert ok and after is cache
    bid = eng._seq[1].blocks[2]
    assert eng._opened == {1: bid} and int(cache["block_table"][1, 2]) != bid
    tok = jnp.asarray([0, int(jnp.argmax(logits[0]))], jnp.int32)
    _, _, cache = eng.decode(params, tok, cache, active=jnp.asarray([False, True]))
    assert eng._opened == {} and int(cache["block_table"][1, 2]) == bid
    # a slot released before its step books nothing for it
    ok, cache = eng.advance_slot(cache, 1)
    cache = eng.release_slot(cache, 1)
    assert eng._opened == {}


def test_a_recycled_tail_block_decodes_like_a_fresh_one(setup):
    """A fresh tail block that comes off the free list holding another
    request's K/V and group stats decodes exactly like a never-used one."""
    cfg, mk, slab, params = setup
    toks = jnp.asarray(np.arange(1, 17, dtype=np.int32)[None])
    active = jnp.asarray([False, True])

    def run(poison):
        eng = Engine(mk(True), n_slots=2, capacity=64)
        cache = eng.new_cache()
        logits, cache = eng.insert(params, cache, toks, 16, slot=1)
        if poison:
            free = list(eng.allocator._free)
            junk = jax.tree.map(lambda a: jnp.full_like(a, 7),
                                eng._read_block(cache, jnp.int32(free[0])))
            for b in free:
                cache = eng._write_block(cache, junk, jnp.int32(b))
        tok = jnp.asarray([0, int(jnp.argmax(logits[0]))], jnp.int32)
        out = []
        for _ in range(10):           # opens fresh blocks at 16 and 24
            ok, cache = eng.advance_slot(cache, 1)
            assert ok
            nxt, lg, cache = eng.decode(params, tok, cache, active=active)
            out.append(np.asarray(lg[1]))
            tok = jnp.asarray([0, int(nxt[1])], jnp.int32)
        return out

    for a, b in zip(run(False), run(True)):
        np.testing.assert_array_equal(a, b)


def test_prefix_hit_skips_prefill_flops_identical_logits(setup):
    """A full-prompt prefix hit replays the cached first-token logits and
    runs zero prefill FLOPs (the cold prefill costs > 0 by flopcount)."""
    from functools import partial

    cfg, mk, slab, params = setup
    bundle = mk(True)
    eng = Engine(bundle, n_slots=2, capacity=64)
    cache = eng.new_cache()
    toks = jnp.asarray(np.arange(5, 16, dtype=np.int32)[None])

    prefill_flops = count_fn_flops(
        partial(bundle.prefill, capacity=64), params,
        {"tokens": toks, "lengths": jnp.array([11], jnp.int32)},
    )
    assert prefill_flops > 0

    cold, cache = eng.insert(params, cache, toks, 11, slot=0)
    assert eng.prefill_count == 1 and eng.prefix_hits == 0
    cache = eng.release_slot(cache, 0)  # blocks park free-cached
    hit, cache = eng.insert(params, cache, toks, 11, slot=1)
    # no prefill ran: the flopcount-measured cost was skipped entirely
    assert eng.prefill_count == 1 and eng.prefix_hits == 1
    np.testing.assert_array_equal(np.asarray(cold), np.asarray(hit))


def test_prefix_shared_blocks_and_cow_divergence(setup):
    """Two concurrent identical prompts: the second admission shares every
    block (one prefill total), the first divergent decode write triggers
    copy-on-write, and both requests' outputs equal cold single runs."""
    cfg, mk, slab, params = setup
    eng = Engine(mk(True), n_slots=2, capacity=64)
    sched = ContinuousScheduler(eng, params, pad_prompt_to=16)
    twin = lambda: [
        Request(rid=0, tokens=[5, 6, 7, 8, 9], max_new=6),
        Request(rid=1, tokens=[5, 6, 7, 8, 9], max_new=6),
    ]
    out = sched.run(twin())
    st = eng.pool_stats()
    assert st["prefills"] == 1 and st["prefix_hits"] == 1, st
    assert st["cow_copies"] >= 1, st  # shared partial tail diverged
    assert out[0] == out[1]
    solo = ContinuousScheduler(
        Engine(mk(True), n_slots=1, capacity=64), params, pad_prompt_to=16
    ).run([Request(rid=0, tokens=[5, 6, 7, 8, 9], max_new=6)])
    assert out[0] == solo[0]


def test_preemption_roundtrip_under_2x_oversubscription(setup):
    """A workload whose summed worst-case contexts exceed the pool by
    >= 2x completes via preemption with outputs identical to an
    unconstrained pool (greedy decode: recompute-on-readmit is exact)."""
    cfg, mk, slab, params = setup
    # capacity 64 / bs 8 → 8 blocks worst case per request; 3 requests =
    # 24 blocks vs 9 usable (pool_blocks=10) → 2.7× oversubscribed
    eng = Engine(mk(True, pool_blocks=10), n_slots=3, capacity=64)
    sched = ContinuousScheduler(eng, params, pad_prompt_to=16)
    out = sched.run(_reqs(3, max_new=25))
    assert sched.preemptions > 0
    assert all(len(v) == 25 for v in out.values())
    big = ContinuousScheduler(
        Engine(mk(True), n_slots=3, capacity=64), params, pad_prompt_to=16
    ).run(_reqs(3, max_new=25))
    assert out == big


def test_scheduler_rejects_overlong_prompt(setup):
    """A prompt longer than engine capacity is rejected with a warning
    instead of writing out of range (slab: dynamic_update_slice clamp
    corruption; paged: table overrun)."""
    cfg, mk, slab, params = setup
    for bundle in (slab, mk(True)):
        eng = Engine(bundle, n_slots=2, capacity=64)
        sched = ContinuousScheduler(eng, params, pad_prompt_to=16)
        reqs = [
            Request(rid=0, tokens=list(range(1, 100)), max_new=4),  # 99 > 64
            Request(rid=1, tokens=[3, 4, 5], max_new=3),
        ]
        with pytest.warns(UserWarning, match="exceeds engine capacity"):
            out = sched.run(reqs)
        assert reqs[0].rejected and out[0] == []
        assert len(out[1]) == 3  # the short request is unaffected


def test_full_capacity_prompt_retires_without_out_of_range_write(setup):
    """A prompt of exactly ``capacity`` tokens admits, emits its prefill
    token, and retires immediately — the first decode step would have
    nowhere to write the token's KV (slab: clamp onto the last prompt
    row; paged: null-block drop)."""
    cfg, mk, slab, params = setup
    for bundle in (slab, mk(True)):
        eng = Engine(bundle, n_slots=2, capacity=64)
        sched = ContinuousScheduler(eng, params, pad_prompt_to=16)
        out = sched.run([Request(rid=0, tokens=list(range(1, 65)), max_new=8)])
        assert len(out[0]) == 1  # prefill token only, then retired
        if eng.paged:
            assert eng.allocator.n_in_use == 0


def test_empty_prompt_does_not_crash_paged_insert(setup):
    """Zero-length prompts take the prefill path with no blocks and no
    hash chain (regression: keys[-1] raised IndexError)."""
    cfg, mk, slab, params = setup
    eng = Engine(mk(True), n_slots=1, capacity=64)
    cache = eng.new_cache()
    toks = jnp.zeros((1, 16), jnp.int32)
    logits, cache = eng.insert(params, cache, toks, 0, slot=0)
    assert logits.shape[0] == 1
    assert eng._seq[0].blocks == [] and eng.allocator.n_in_use == 0


def test_admit_samples_prefill_token_from_rng_stream(setup, monkeypatch):
    """Regression (satellite): _admit used to argmax the prefill logits
    even at temperature > 0 — now the first token goes through
    sample_token with a key split off the scheduler rng stream."""
    from repro.serving import SamplingConfig
    import repro.serving.engine as engine_mod

    cfg, mk, slab, params = setup
    seen = []
    orig = engine_mod.sample_token

    def spy(rng, logits, scfg):
        seen.append((np.asarray(rng).copy(), logits.shape[0]))
        return orig(rng, logits, scfg)

    monkeypatch.setattr(engine_mod, "sample_token", spy)
    eng = Engine(slab, n_slots=2, capacity=64,
                 sampling=SamplingConfig(temperature=1.0, top_k=4))
    sched = ContinuousScheduler(eng, params, pad_prompt_to=16)
    sched.run([Request(rid=i, tokens=[3 + i, 4 + i], max_new=3) for i in range(2)])
    # one B=1 call per admission (the prefill token), distinct keys across
    # every sampled draw
    admit_calls = [k for k, b in seen if b == 1]
    assert len(admit_calls) == 2
    keys = {tuple(k.tolist()) for k, _ in seen}
    assert len(keys) == len(seen), "sampling rng key reused"
