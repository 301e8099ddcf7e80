"""Compile-only checks of the served FIER path for a TPU v5e, with no chip.

The Pallas kernels run in interpret mode everywhere else in the suite,
which never shows what Mosaic refuses: slices off the (8, 128) tiling,
SMEM block shapes, VMEM over-use.  Here the two paged FIER kernels, the
whole olmo-1b decode step and the DP=2 x TP=2 chunked prefill are
compiled for a *described* v5e (``jax.experimental.topologies``) at
olmo-1b's published widths.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under several pytest workers a
module that probed it while being collected would hand the workers
different test sets.  Where it cannot be described, the tests skip.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import fused_retrieval as fr
from repro.kernels import ops
from repro.kernels import sparse_attention as sa

CFG = get_config("olmo-1b")
HKV, D = CFG.n_kv_heads, CFG.d_head
REP = CFG.n_heads // CFG.n_kv_heads
SLOTS, BLOCK, GROUP = 4, 32, 32
# (pool capacity per slot, FIER budget): the chip smoke run's point and a
# 32k context at the same ~12% budget
POINTS = [(8192, 1024), (32768, 4096)]
# one v5e chip's HBM
HBM_BYTES = 16e9


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


@pytest.mark.parametrize("capacity,budget", POINTS)
def test_paged_retrieve_compiles(chip, capacity, budget):
    nb = capacity // BLOCK
    n_blocks = SLOTS * nb + 1
    args = (
        _sds(chip, (SLOTS, HKV, REP, D), jnp.float32),
        _sds(chip, (n_blocks, BLOCK // 8, HKV, D), jnp.uint8),
        _sds(chip, (n_blocks, BLOCK // GROUP, HKV, D), jnp.bfloat16),
        _sds(chip, (n_blocks, BLOCK // GROUP, HKV, D), jnp.bfloat16),
        _sds(chip, (SLOTS, nb), jnp.int32),
        _sds(chip, (SLOTS,), jnp.int32),
    )

    def f(q, codes, scale, zero, table, lengths):
        return fr.paged_fused_retrieve_hm(
            q, codes, scale, zero, table, lengths, budget, group=GROUP,
            block_size=BLOCK, sink=4, recent=64, interpret=False,
        )

    assert _custom_calls(jax.jit(f).lower(*args).compile()) == 1


@pytest.mark.parametrize("capacity,budget", POINTS)
def test_paged_attend_compiles(chip, capacity, budget):
    nb = capacity // BLOCK
    n_blocks = SLOTS * nb + 1
    args = (
        _sds(chip, (SLOTS, HKV, REP, D), jnp.bfloat16),
        _sds(chip, (n_blocks, BLOCK, HKV, D), jnp.bfloat16),
        _sds(chip, (n_blocks, BLOCK, HKV, D), jnp.bfloat16),
        _sds(chip, (SLOTS, nb), jnp.int32),
        _sds(chip, (SLOTS, HKV, budget), jnp.int32),
        _sds(chip, (SLOTS, HKV, 1, budget), jnp.int8),
    )

    def f(q, k_pool, v_pool, table, idx, mask):
        return sa.paged_fused_sparse_attention_hm(
            q, k_pool, v_pool, table, idx, mask, block_size=BLOCK,
            blk_k=ops.ATTEND_BLK, interpret=False,
        )

    assert _custom_calls(jax.jit(f).lower(*args).compile()) == 1


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
