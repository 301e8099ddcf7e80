"""Compile-only checks of the served FIER path for a TPU v5e, with no chip.

The Pallas kernels run in interpret mode everywhere else in the suite,
which never shows what Mosaic refuses: slices off the (8, 128) tiling,
SMEM block shapes, VMEM over-use.  Here the two paged FIER kernels, the
whole olmo-1b decode step and the DP=2 x TP=2 chunked prefill are
compiled for a *described* v5e (``jax.experimental.topologies``) at
olmo-1b's published widths; and at Qwen3-MoE's share (GQA 16:1, QK-norm,
8 of 128 experts held) both FIER kernels, the ``held_experts_swiglu``
kernel and the whole decode step, at 8 slots x 24576 tokens, budget 2048.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under several pytest workers a
module that probed it while being collected would hand the workers
different test sets.  Where it cannot be described, the tests skip.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import fused_retrieval as fr
from repro.kernels import held_experts as he
from repro.kernels import ops
from repro.kernels import sparse_attention as sa

CFG = get_config("olmo-1b")
HKV, D = CFG.n_kv_heads, CFG.d_head
REP = CFG.n_heads // CFG.n_kv_heads
SLOTS, BLOCK, GROUP = 4, 32, 32
# (pool capacity per slot, FIER budget): the chip smoke run's point, a
# 32k context at the same ~12% budget, and the benchmark cell
# olmo-1b.longctx_decode's point
POINTS = [(8192, 1024), (32768, 4096), (12288, 1024)]
# one v5e chip's HBM
HBM_BYTES = 16e9
# Qwen3-235B-A22B at one chip's share (bench/configs/qwen3-moe-235b-a22b.json):
# the first 8 layers, experts 0-7 of 128 held, 8 slots x 24576 tokens,
# budget 2048
QWEN = dataclasses.replace(get_config("qwen3-moe-235b-a22b"), n_layers=8, experts_held=8)
QWEN_SLOTS, QWEN_CAPACITY, QWEN_BUDGET = 8, 24576, 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # the TPU compiler logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _sds(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _kernel_ops(compiled) -> set[str]:
    """The Mosaic custom calls' instruction names less their ``.<n>``: the
    names the device trace gives the kernels' ops, which the benchmark's
    roofline readers match (``%paged_fused_retrieve_hm.10``)."""
    return {m.rsplit(".", 1)[0] for m in re.findall(
        r'^\s*(?:ROOT )?%([\w.-]+) = .*custom_call_target="tpu_custom_call"',
        compiled.as_text(), re.M)}


def _retrieve_ops(chip, slots, hkv, rep, capacity, budget):
    nb = capacity // BLOCK
    n_blocks = slots * nb + 1
    args = (
        _sds(chip, (slots, hkv, rep, D), jnp.float32),
        _sds(chip, (n_blocks, BLOCK // 8, hkv, D), jnp.uint8),
        _sds(chip, (n_blocks, BLOCK // GROUP, hkv, D), jnp.bfloat16),
        _sds(chip, (n_blocks, BLOCK // GROUP, hkv, D), jnp.bfloat16),
        _sds(chip, (slots, nb), jnp.int32),
        _sds(chip, (slots,), jnp.int32),
    )

    def f(q, codes, scale, zero, table, lengths):
        return fr.paged_fused_retrieve_hm(
            q, codes, scale, zero, table, lengths, budget, group=GROUP,
            block_size=BLOCK, sink=4, recent=64, interpret=False,
        )

    compiled = jax.jit(f).lower(*args).compile()
    assert _custom_calls(compiled) == 1
    return _kernel_ops(compiled)


def _attend_ops(chip, slots, hkv, rep, capacity, budget):
    nb = capacity // BLOCK
    n_blocks = slots * nb + 1
    args = (
        _sds(chip, (slots, hkv, rep, D), jnp.bfloat16),
        _sds(chip, (n_blocks, BLOCK, hkv, D), jnp.bfloat16),
        _sds(chip, (n_blocks, BLOCK, hkv, D), jnp.bfloat16),
        _sds(chip, (slots, nb), jnp.int32),
        _sds(chip, (slots, hkv, budget), jnp.int32),
        _sds(chip, (slots, hkv, 1, budget), jnp.int8),
    )

    def f(q, k_pool, v_pool, table, idx, mask):
        return sa.paged_fused_sparse_attention_hm(
            q, k_pool, v_pool, table, idx, mask, block_size=BLOCK,
            blk_k=ops.ATTEND_BLK, interpret=False,
        )

    compiled = jax.jit(f).lower(*args).compile()
    assert _custom_calls(compiled) == 1
    return _kernel_ops(compiled)


@pytest.mark.parametrize("capacity,budget", POINTS)
def test_paged_retrieve_compiles(chip, capacity, budget):
    assert _retrieve_ops(chip, SLOTS, HKV, REP, capacity, budget) == {
        "paged_fused_retrieve_hm"}


@pytest.mark.parametrize("capacity,budget", POINTS)
def test_paged_attend_compiles(chip, capacity, budget):
    assert _attend_ops(chip, SLOTS, HKV, REP, capacity, budget) == {
        "paged_fused_sparse_attention_hm"}


def test_paged_retrieve_compiles_gqa_16(chip):
    assert _retrieve_ops(chip, QWEN_SLOTS, QWEN.n_kv_heads, QWEN.n_heads // QWEN.n_kv_heads,
                         QWEN_CAPACITY, QWEN_BUDGET) == {"paged_fused_retrieve_hm"}


def test_paged_attend_compiles_gqa_16(chip):
    assert _attend_ops(chip, QWEN_SLOTS, QWEN.n_kv_heads, QWEN.n_heads // QWEN.n_kv_heads,
                       QWEN_CAPACITY, QWEN_BUDGET) == {"paged_fused_sparse_attention_hm"}


@pytest.mark.parametrize("blk_k,fits", [(ops.ATTEND_BLK, True), (1024, False)])
def test_attend_vmem_guard(blk_k, fits):
    """The select-and-attend gather's two buffers of all-heads rows are
    checked against the scoped VMEM limit while tracing: olmo-1b's 16 KV
    heads at budget 4096 fit at the served block; a block of 1024 rows
    (32 MiB with its f32 copies) is refused, naming its shape."""
    slots, capacity, budget = SLOTS, 32768, 4096
    sds = jax.ShapeDtypeStruct
    n_blocks = slots * (capacity // BLOCK) + 1
    args = (
        sds((slots, HKV, REP, D), jnp.bfloat16),
        sds((n_blocks, BLOCK, HKV, D), jnp.bfloat16),
        sds((n_blocks, BLOCK, HKV, D), jnp.bfloat16),
        sds((slots, capacity // BLOCK), jnp.int32),
        sds((slots, HKV, budget), jnp.int32),
        sds((slots, HKV, 1, budget), jnp.int8),
    )

    def f(*a):
        return sa.paged_fused_sparse_attention_hm(*a, block_size=BLOCK, blk_k=blk_k)

    if fits:
        assert jax.eval_shape(f, *args).shape == (slots, HKV, REP, D)
    else:
        with pytest.raises(ValueError, match=f"blk_k={blk_k}, Hkv={HKV}, D={D}"):
            jax.eval_shape(f, *args)


@pytest.mark.parametrize("tokens", [QWEN_SLOTS, 2048])
def test_held_experts_compiles(chip, tokens):
    """A decode step's tokens, and a prefill chunk's, through the held
    experts at Qwen3-MoE's widths."""
    d, ff, held = QWEN.d_model, QWEN.d_ff, QWEN.n_held
    args = (_sds(chip, (tokens, d), jnp.bfloat16), _sds(chip, (tokens, held), jnp.float32),
            _sds(chip, (held, d, ff), jnp.bfloat16), _sds(chip, (held, d, ff), jnp.bfloat16),
            _sds(chip, (held, ff, d), jnp.bfloat16))

    def f(x, g, w1, w3, w2):
        return he.held_experts_swiglu(x, g, w1, w3, w2, interpret=False)

    compiled = jax.jit(f).lower(*args).compile()
    assert _custom_calls(compiled) == 1
    assert _kernel_ops(compiled) == {"held_experts_swiglu"}


def test_decode_step_compiles_and_fits(chip, monkeypatch):
    """The served decode step (paged pool, one_pass pipeline) at olmo-1b
    full width, 4 slots x 8192 tokens: both FIER kernels are in it as
    Mosaic custom calls, and params + pool + temporaries fit one chip."""
    from repro.serving import Engine
    from repro.serving.engine import serving_policy

    # the wrappers pick interpret mode from the (CPU) default backend
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    capacity, budget = POINTS[0]
    eng = Engine.build(
        CFG, n_slots=SLOTS, capacity=capacity,
        policy=serving_policy(budget=budget, pipeline="one_pass"),
        layout="paged", block_size=BLOCK,
    )
    on_chip = lambda tree: jax.tree.map(
        lambda a: _sds(chip, a.shape, a.dtype), tree
    )
    params = on_chip(jax.eval_shape(eng.bundle.init, jax.random.PRNGKey(0)))
    cache = on_chip(
        jax.eval_shape(lambda: eng.bundle.init_cache(SLOTS, capacity, 0))
    )
    token = _sds(chip, (SLOTS,), jnp.int32)
    compiled = jax.jit(eng.bundle.decode_step, donate_argnums=(2,)).lower(
        params, token, cache
    ).compile()
    txt = compiled.as_text()
    for name in ("paged_fused_retrieve_hm", "paged_fused_sparse_attention_hm"):
        assert any(
            f"jit({name})" in ln and "tpu_custom_call" in ln
            for ln in txt.splitlines()
        ), name
    assert {"paged_fused_retrieve_hm",
            "paged_fused_sparse_attention_hm"} <= _kernel_ops(compiled)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, mem


def test_sharded_prefill_keeps_reduction_order(topo):
    """DP=2 x TP=2 chunked prefill on a v5e 2x2: no matmul is left to
    contract over a mesh-split axis (that would all-reduce partial sums in
    bf16 and part the sharded engine's tokens from one device's)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.serving import Engine
    from repro.serving.engine import serving_policy

    capacity = 1024
    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    eng = Engine.build(
        CFG, n_slots=SLOTS, capacity=capacity,
        policy=serving_policy(budget=256, pipeline="one_pass"),
        layout="paged", block_size=BLOCK, mesh=mesh,
    )
    at = lambda spec: NamedSharding(mesh, spec)
    shaped = lambda a, spec: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=at(spec))
    params = jax.tree.map(
        lambda a: shaped(a, P()),
        jax.eval_shape(eng.bundle.init, jax.random.PRNGKey(0)),
    )
    cache = {}
    for name, val in jax.eval_shape(
        lambda: eng.bundle.init_cache(SLOTS, capacity, 0)
    ).items():
        if name == "block_table":
            cache[name] = shaped(val, P("data", None))
        elif name == "length":
            cache[name] = shaped(val, P("data"))
        else:   # layer-stacked pools [L, N, bs, Hkv, D]
            cache[name] = jax.tree.map(
                lambda a: shaped(a, P(None, "data", None, "model", None)), val
            )
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=at(P()))
    batch = {
        "tokens": jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=at(P())),
        "start": scalar, "slot": scalar, "total": scalar,
        "table_row": jax.ShapeDtypeStruct(
            (capacity // BLOCK,), jnp.int32, sharding=at(P())
        ),
    }
    fn = jax.jit(functools.partial(eng.bundle.prefill_chunk, final=False))
    txt = fn.lower(params, batch, cache).compile().as_text()
    partial_sums = [ln for ln in txt.splitlines()
                    if "all-reduce(" in ln and "dot_general" in ln]
    assert not partial_sums, partial_sums[0][:300]


def test_qwen_decode_step_compiles_and_fits(chip, monkeypatch):
    """The served decode step at Qwen3-MoE's share and sizes above: QK-
    norm, GQA 16:1 through both FIER kernels, the held experts' kernel,
    and bf16 params + the 8 x 24576-token pool + temporaries in one chip."""
    from repro.serving import Engine
    from repro.serving.engine import serving_policy

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    pol = dataclasses.replace(
        serving_policy(budget=QWEN_BUDGET, group=GROUP, skip_layers=2, sink=4, recent=64,
                       pipeline="one_pass", layout="paged"), block_size=BLOCK)
    eng = Engine.build(QWEN, n_slots=QWEN_SLOTS, capacity=QWEN_CAPACITY, policy=pol,
                       layout="paged", block_size=BLOCK)
    on_chip = lambda tree: jax.tree.map(lambda a: _sds(chip, a.shape, a.dtype), tree)
    params = on_chip(jax.eval_shape(eng.bundle.init, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(
        lambda: eng.bundle.init_cache(QWEN_SLOTS, QWEN_CAPACITY, 0)))
    # as served: with the step's fresh tail blocks (Engine._open_blocks)
    compiled = eng._decode_active.lower(
        params, _sds(chip, (QWEN_SLOTS,), jnp.int32), cache,
        _sds(chip, (QWEN_SLOTS,), jnp.bool_), _sds(chip, (QWEN_SLOTS,), jnp.int32)).compile()
    assert _kernel_ops(compiled) == {"paged_fused_retrieve_hm",
                                     "paged_fused_sparse_attention_hm",
                                     "held_experts_swiglu"}
    # the pool is updated in place: no copy of a pool leaf (whose axis 1
    # is the pool's blocks) to another layout and back
    n_blocks = jax.tree.leaves(cache["front"])[0].shape[1]
    pool_copies = re.findall(rf"^\s*(?:ROOT )?%copy[\w.]* = \w+\[\d+,{n_blocks},.*",
                             compiled.as_text(), re.M)
    assert not pool_copies, pool_copies[0][:200]
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, mem
