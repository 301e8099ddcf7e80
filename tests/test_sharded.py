"""Sharded multi-device serving (DESIGN.md §Sharded serving).

Fast lane: ShardSpec / DecodePlan capability validation, the per-shard
ShardedBlockAllocator behind the global-id surface, and a hypothesis
property tying exact-mode sharded selection to the single-device top-k
oracle.  Slow lane (forced-multi-device subprocesses): engine-level
TP×DP decode bit-identity vs the single-device oracle, per-shard
score-byte gating of the one-pass pipeline, and a seeded chaos pass on
the DP-sharded layout.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# unlike test_property.py this module holds more than property tests, so
# a missing hypothesis skips only the selection-equivalence property
# (declared in the `test` extra; CI installs it) instead of the module
try:
    from hypothesis import assume, given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:  # pragma: no cover - CI always has it
    st = None

from conftest import run_in_subprocess
from repro.configs import reduced_config
from repro.core import policy as core_policy
from repro.core import retrieval as rt
from repro.core.policy import (
    AttentionBackend,
    DecodePlan,
    PolicyConfig,
    UnsupportedPlanError,
    register_backend,
)
from repro.kvcache.paged import AllocatorAuditError
from repro.kvcache.sharded import ShardSpec, ShardedBlockAllocator
from repro.launch.mesh import make_mesh
from repro.serving import Engine

_BENCH_DIR = os.path.join(os.path.dirname(__file__), "..", "benchmarks")


def _mesh11():
    # single-device mesh: enough for spec/plan validation in the fast lane
    return make_mesh((1, 1), ("data", "model"))


def _pol(kind="fier", layout="paged", pipeline="reference", block_size=8):
    return PolicyConfig(
        kind=kind, budget=16, group=8, skip_layers=1, sink=2, recent=4,
        pipeline=pipeline, layout=layout, block_size=block_size,
    )


# ------------------------------------------------------------- ShardSpec

def test_shard_spec_validation():
    m = _mesh11()
    spec = ShardSpec(mesh=m, tp_axes=("model",), dp_axes=("data",))
    assert spec.n_tp == 1 and spec.n_dp == 1 and spec.mode == "exact"
    with pytest.raises(ValueError, match="mode"):
        ShardSpec(mesh=m, tp_axes=("model",), mode="approx")
    with pytest.raises(ValueError, match="not in mesh"):
        ShardSpec(mesh=m, tp_axes=("expert",))
    with pytest.raises(ValueError, match="both tp and dp"):
        ShardSpec(mesh=m, tp_axes=("model",), dp_axes=("model",))
    with pytest.raises(ValueError, match="at least one"):
        ShardSpec(mesh=m)


# ----------------------------------------------------- plan capabilities

def test_plan_accepts_sharding_capable_backends():
    spec = ShardSpec(mesh=_mesh11(), tp_axes=("model",), dp_axes=("data",))
    for kind in ("fier", "full"):
        plan = DecodePlan.build(_pol(kind=kind), shard=spec)
        assert plan.shard is spec
        # re-resolution keeps the spec on the plan
        assert plan.with_pipeline(plan.pipeline).shard is spec
    # shard-free build is unchanged
    assert DecodePlan.build(_pol()).shard is None


def test_plan_sharding_requires_paged_layout():
    spec = ShardSpec(mesh=_mesh11(), tp_axes=("model",))
    with pytest.raises(UnsupportedPlanError, match="requires layout='paged'"):
        DecodePlan.build(_pol(layout="slab"), shard=spec)


def test_plan_error_names_axes_and_backend_modes():
    """Satellite: a backend without the requested sharding mode fails
    plan validation with the offending mesh axes AND the backend's
    ``supports_sharding`` entry in the message."""
    backend = AttentionBackend(
        name="_testonly_unsharded",
        supports=frozenset({("paged", "reference")}),
        build_metadata=lambda K, cfg: None,
        update_metadata=lambda meta, K, pos, cfg: meta,
        decode=lambda q, view, plan: q,
        needs_metadata=False,
    )
    register_backend(backend)
    try:
        spec = ShardSpec(
            mesh=_mesh11(), tp_axes=("model",), dp_axes=("data",)
        )
        with pytest.raises(UnsupportedPlanError) as exc:
            DecodePlan.build(_pol(kind="_testonly_unsharded"), shard=spec)
        msg = str(exc.value)
        assert "('model', 'data')" in msg        # the offending mesh axes
        assert "mode='exact'" in msg             # the requested mode
        assert "sharding modes: -" in msg        # the backend's capability
    finally:
        del core_policy._REGISTRY["_testonly_unsharded"]
        core_policy.POLICIES = tuple(core_policy._REGISTRY)


def test_backend_registration_rejects_bad_sharding_modes():
    backend = AttentionBackend(
        name="_testonly_badmode",
        supports=frozenset({("slab", "reference")}),
        build_metadata=lambda K, cfg: None,
        update_metadata=lambda meta, K, pos, cfg: meta,
        decode=lambda q, view, plan: q,
        supports_sharding=frozenset({"approximate"}),
    )
    with pytest.raises(ValueError, match="invalid sharding modes"):
        register_backend(backend)


def test_engine_build_mesh_validation():
    cfg = reduced_config("olmo-1b")
    with pytest.raises(ValueError, match="layout='paged'"):
        Engine.build(cfg, n_slots=2, capacity=64, policy=_pol(layout="slab"),
                     mesh=_mesh11())
    with pytest.raises(ValueError, match="must be named"):
        Engine.build(cfg, n_slots=2, capacity=64,
                     policy=_pol(), layout="paged",
                     mesh=make_mesh((1,), ("expert",)))


# -------------------------------------------------- ShardedBlockAllocator

def test_sharded_allocator_routing_and_admission():
    a = ShardedBlockAllocator(8, 16, n_shards=2)
    assert a.n_local == 4 and a.usable == 3 and a.n_free == 3
    # local row 0 is each shard's null block: gids 0 and 4 never allocated
    got0 = [a.alloc(shard=0) for _ in range(3)]
    assert sorted(got0) == [1, 2, 3]
    assert a.alloc(shard=0) is None
    # admission accounting is the per-device MINIMUM: shard 1 still has 3
    # free blocks but an admitted request may land on the exhausted shard
    assert a.n_free == 0 and a.n_in_use == 3
    got1 = [a.alloc(shard=1) for _ in range(3)]
    assert sorted(got1) == [5, 6, 7]
    for gid in got0 + got1:
        assert a.ref[gid] == 1
        assert a.home(gid) == (0 if gid < 4 else 1)
        a.free(gid)
    assert a.n_in_use == 0 and a.n_free == 3
    assert sorted(a._free) == [1, 2, 3, 5, 6, 7]
    a.audit()
    with pytest.raises(ValueError, match="not divisible"):
        ShardedBlockAllocator(9, 16, n_shards=2)


def test_sharded_allocator_prefix_cache_is_shard_local():
    a = ShardedBlockAllocator(8, 16, n_shards=2)
    b = a.alloc(shard=1)
    a.register(b, 42)
    assert a.ref[b] == 1
    assert a.lookup(42, shard=1) == b and a.ref[b] == 2
    assert a.lookup(42, shard=0) is None     # shard-local: no cross revive
    assert a.key_of(b) == 42 and a.key_resident(42)
    a.free(b)
    a.free(b)
    # parked free-cached on shard 1: still hittable there, counted free
    assert a.ref[b] == 0 and a.n_free == 3 and a.n_parked == 1
    assert a.lookup(42, shard=1) == b
    a.free(b)
    assert a.drop_key(42) == b
    assert not a.key_resident(42)
    a.audit()


def test_sharded_allocator_peek_is_conservative_without_home_shard():
    a = ShardedBlockAllocator(8, 16, n_shards=2)
    b = a.alloc(shard=0)
    a.register(b, 7)
    a.free(b)                                # ref 0: parked free-cached
    # admission sizing before the slot (hence home shard) is known: no-hit
    assert a.peek([7]) == (0, 0)
    assert a.peek_prefix([7]) == []
    assert a.blocks_needed(33) == 3
    # with the home shard: the inner allocator's real answer
    assert a.peek([7], shard=0) == (1, 1)
    assert a.peek([7], shard=1) == (0, 0)
    # parked hit: the revival still comes out of the free pool (3-1+1)
    assert a.blocks_needed(33, keys=[7], shard=0) == 3
    assert a.lookup(7, shard=0) == b         # revive: now a live hit
    assert a.blocks_needed(33, keys=[7], shard=0) == 2
    a.free(b)
    a.audit()


def test_sharded_allocator_audit_splits_owners_and_detects_drift():
    a = ShardedBlockAllocator(8, 16, n_shards=2)
    b0, b1 = a.alloc(shard=0), a.alloc(shard=1)
    a.audit({b0: 1, b1: 1})
    with pytest.raises(AllocatorAuditError, match="ref-count drift"):
        a.audit({b0: 1, b1: 2})
    a.free(b0)
    a.free(b1)
    a.audit({})


def test_sharded_allocator_ttl_eviction_globalizes_ids():
    t = [0.0]
    a = ShardedBlockAllocator(8, 16, n_shards=2, park_ttl=5.0)
    a.set_clock(lambda: t[0])
    a.record_evictions = True
    b = a.alloc(shard=1)
    a.register(b, 99)
    a.free(b)                                # ref 0: parked, TTL running
    t[0] = 6.0
    assert a.expire_parked() == 1
    evs = a.take_evicted()
    assert [(e.bid, e.key, e.reason) for e in evs] == [(b, 99, "ttl")]
    assert b >= a.n_local                    # global id, not the local one
    assert a.take_evicted() == []
    a.audit()


def test_sharded_allocator_fail_next_and_stats():
    a = ShardedBlockAllocator(8, 16, n_shards=2)
    a.fail_next(1)
    assert a.alloc(shard=1) is None
    assert a.injected_alloc_failures == 1
    b = a.alloc(shard=1)
    assert b is not None
    st_all = a.stats()
    assert st_all["pool_shards"] == 2
    assert st_all["pool_blocks_total"] == 8
    assert st_all["pool_blocks_usable"] == 6
    assert st_all["pool_blocks_in_use"] == 1
    assert st_all["pool_injected_alloc_failures"] == 1
    per = a.shard_stats()
    assert len(per) == 2
    assert per[0]["pool_blocks_in_use"] == 0
    assert per[1]["pool_blocks_in_use"] == 1
    a.free(b)
    a.audit()


# ------------------------------------------- exact-mode selection property

if st is not None:
    @st.composite
    def _selection_cases(draw):
        n_shards = draw(st.sampled_from([1, 2, 4]))
        hq, hkv = draw(st.sampled_from([(4, 4), (4, 2), (8, 2)]))
        s_loc = draw(st.integers(2, 10))
        S = n_shards * s_loc
        budget = draw(st.integers(1, S))
        length = draw(st.integers(1, S))
        ties = draw(st.booleans())
        if ties:
            flat = draw(
                st.lists(st.integers(0, 4), min_size=hq * S, max_size=hq * S)
            )
        else:
            flat = draw(st.permutations(list(range(hq * S))))
        scores = np.asarray(flat, np.float32).reshape(1, hq, S)
        return n_shards, hq, hkv, s_loc, budget, length, scores, ties


def _sharded_exact_select(kv, length, budget, n_shards, s_loc):
    """Mirror of ``dist.fier_decode_sharded``'s exact mode (the shard_map
    body in core/distributed.py), flattened to host numpy: per-shard
    top-``k_cand`` nomination, all-gather of candidate scores, global
    budget-th threshold, keep candidates >= threshold."""
    Hkv = kv.shape[1]
    local_budget = max(budget // n_shards, 1)
    k_cand = min(max(local_budget * 2, 1) if n_shards > 1 else budget, s_loc)
    cand_s, cand_i = [], []
    for j in range(n_shards):
        s = kv[0, :, j * s_loc:(j + 1) * s_loc].copy()
        local_len = min(max(length - j * s_loc, 0), s_loc)
        s[:, local_len:] = rt.NEG_INF
        # lax.top_k semantics: descending, ties broken by lower index
        order = np.lexsort((np.arange(s_loc)[None, :].repeat(Hkv, 0), -s))
        idx = order[:, :k_cand]
        cand_s.append(np.take_along_axis(s, idx, axis=1))
        cand_i.append(idx + j * s_loc)
    all_s = np.concatenate(cand_s, axis=1)
    all_i = np.concatenate(cand_i, axis=1)
    kth = -np.sort(-all_s, axis=1)[:, min(budget, all_s.shape[1]) - 1]
    keep = (all_s >= kth[:, None]) & (all_s > rt.NEG_INF / 2)
    return [set(all_i[h][keep[h]].tolist()) for h in range(Hkv)], kth


def _selection_property(case):
    """Exact-mode sharded selection returns the same index set as the
    single-device ``select_topk`` oracle — exactly under distinct scores
    (given the nomination condition), and up to τ-ties otherwise."""
    n_shards, hq, hkv, s_loc, budget, length, scores, ties = case
    S = n_shards * s_loc
    kv = np.asarray(rt.reduce_over_query_group(jnp.asarray(scores), hkv))

    # single-device oracle (the real library function)
    idx = np.asarray(
        rt.select_topk(jnp.asarray(kv), min(budget, S),
                       jnp.asarray([length], jnp.int32))
    )
    oracle = [
        {int(i) for i in idx[0, h] if i < length} for h in range(hkv)
    ]

    got, kth = _sharded_exact_select(kv, length, budget, n_shards, s_loc)

    # nomination condition: every shard must be able to surface all of
    # its tokens scoring >= the global budget-th score (2× fair-share
    # candidate cap) — hypothesis discards draws that violate it
    local_budget = max(budget // n_shards, 1)
    k_cand = min(max(local_budget * 2, 1) if n_shards > 1 else budget, s_loc)
    for h in range(hkv):
        valid = kv[0, h, :length]
        eff = min(budget, length)
        tau = -np.sort(-valid)[eff - 1]
        for j in range(n_shards):
            lo, hi = j * s_loc, min((j + 1) * s_loc, length)
            assume(int((kv[0, h, lo:hi] >= tau).sum()) <= k_cand)

    for h in range(hkv):
        if not ties:
            assert got[h] == oracle[h], (h, kth[h])
        else:
            diff = got[h] ^ oracle[h]
            assert all(kv[0, h, i] == kth[h] for i in diff), (h, diff)


if st is not None:
    test_exact_mode_selection_matches_single_device_topk = settings(
        max_examples=40, deadline=None
    )(given(_selection_cases())(_selection_property))
else:  # keep the skip visible in reports when hypothesis is absent
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_exact_mode_selection_matches_single_device_topk():
        pass


# =====================================================================
# multi-device subprocess lane (auto-marked slow by conftest: the
# literal ``run_in_subprocess`` below is the marker trigger)
# =====================================================================

_DRIVER = """
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.configs import reduced_config
from repro.serving import Engine
from repro.serving.engine import serving_policy

cfg = reduced_config("olmo-1b")

def run(mesh, pipeline="reference", kind="fier", slot=0, chunked=False):
    pol = serving_policy(budget=64, skip_layers=1, recent=32, pipeline=pipeline)
    if kind != "fier":
        pol = dataclasses.replace(pol, kind=kind)
    eng = Engine.build(cfg, n_slots=4, capacity=256, policy=pol,
                       layout="paged", block_size=32, pool_blocks=40, mesh=mesh)
    params = eng.bundle.init(jax.random.PRNGKey(0))
    cache = eng.new_cache()
    toks = (np.arange(50) * 7 % 97).astype(np.int32)
    if chunked:
        resume, cache = eng.begin_chunked(cache, slot, toks)
        pos = resume
        while pos < 50:
            n = min(24, 50 - pos)
            ok, pre, cache = eng.prefill_chunk(params, cache, slot, toks, pos, n)
            assert ok
            pos += n
    else:
        pre, cache = eng.insert(params, cache, jnp.asarray(toks[None, :]), 50, slot)
    tok = int(jnp.argmax(pre[0]))
    outs = [tok]
    tvec = jnp.zeros((4,), jnp.int32)
    active = jnp.zeros((4,), bool).at[slot].set(True)
    for _ in range(6):
        ok, cache = eng.advance_slot(cache, slot)
        assert ok
        nxt, lg, cache = eng.decode(params, tvec.at[slot].set(tok), cache,
                                    active=active)
        tok = int(nxt[slot])
        outs.append(tok)
    cache = eng.release_slot(cache, slot)
    eng.audit()
    assert eng.allocator.n_in_use == 0
    return outs, np.asarray(pre), np.asarray(lg[slot])

def check(name, base, got):
    assert got[0] == base[0], (name, got[0], base[0])
    assert np.array_equal(got[1], base[1]), name + ": prefill logits drifted"
    assert np.array_equal(got[2], base[2]), name + ": decode logits drifted"
    print(name, "bit-identical")
"""


def test_sharded_decode_bit_identical_to_oracle():
    """TP=2, DP=2 and TP×DP engines produce bit-identical prefill
    logits, decode logits, and token streams vs the single-device
    oracle (fier backend, reference pipeline), with a clean audit."""
    run_in_subprocess(_DRIVER + """
base = run(None)
check("tp2", base, run(make_mesh((2,), ("model",))))
check("dp2", base, run(make_mesh((2,), ("data",))))
check("tp2xdp2", base, run(make_mesh((2, 2), ("data", "model"))))
""")


def test_sharded_pipelines_and_backends_bit_identical():
    """The one-pass FIER kernel pipeline and the full-KV backend run
    sharded through the same plan surface; a slot homed on DP shard 1
    is bit-identical to the slot-0 single-device run."""
    run_in_subprocess(_DRIVER + """
mesh = make_mesh((2, 2), ("data", "model"))
for kind, pipeline in [("fier", "one_pass"), ("full", "reference")]:
    base = run(None, pipeline=pipeline, kind=kind)
    got = run(mesh, pipeline=pipeline, kind=kind, slot=3)
    check(f"{kind}/{pipeline} slot3", base, got)
""")


def test_sharded_chunked_prefill_bit_identical():
    """Chunked admission on the sharded pool: the per-chunk pool
    gather/scatter round-trip must stay bit-identical to the unsharded
    chunked run (the gathered K/V are re-replicated before attention)."""
    run_in_subprocess(_DRIVER + """
base = run(None, chunked=True)
check("chunked tp2xdp2",
      base, run(make_mesh((2, 2), ("data", "model")), chunked=True,
                slot=2))
""")


def test_sharded_tp_divisibility_error():
    run_in_subprocess(_DRIVER + """
mesh3 = make_mesh((3,), ("model",))
try:
    Engine.build(cfg, n_slots=2, capacity=64,
                 policy=serving_policy(budget=16, skip_layers=1),
                 layout="paged", mesh=mesh3)
except ValueError as e:
    assert "divisible" in str(e) and "model" in str(e), e
else:
    raise AssertionError("n_kv_heads=4 with TP=3 must be rejected")
print("divisibility error OK")
""")


def test_sharded_one_pass_zero_score_bytes_per_shard():
    """The sharded one-pass decode keeps per-token score tensors out of
    HBM on every shard: the jaxpr byte counter (which recurses into the
    shard_map body per device) reports exactly zero, while the reference
    pipeline on the same sharded layout is nonzero (the counter is not
    vacuous under shard_map)."""
    run_in_subprocess("""
import sys
sys.path.insert(0, %r)
from flopcount import count_fn_score_bytes
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import quantize as qz
from repro.core.policy import DecodePlan, PolicyConfig
from repro.kvcache.sharded import ShardSpec, sharded_paged_decode_step

B, S, Hkv, Hq, D, g, bs = 2, 256, 2, 4, 32, 8, 32
nb = S // bs
n_dp = 2
n_local = nb + 1
N = n_dp * n_local
ks = jax.random.split(jax.random.PRNGKey(0), 5)
K = jax.random.normal(ks[0], (B, S, Hkv, D), jnp.bfloat16)
V = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.bfloat16)
q = jax.random.normal(ks[2], (B, Hq, D), jnp.bfloat16)
k_new = jax.random.normal(ks[3], (B, 1, Hkv, D), jnp.bfloat16)
v_new = jax.random.normal(ks[4], (B, 1, Hkv, D), jnp.bfloat16)
qk = qz.quantize(K.astype(jnp.float32), g)

# batch row b's blocks live on its home DP shard: gids b*n_local+1 ..
table = jnp.asarray(
    [[b * n_local + 1 + i for i in range(nb)] for b in range(B)], jnp.int32
)

def to_pool(arr):
    pb = arr.shape[1] // nb     # side-car leaves carry S//g rows, not S
    pool = jnp.zeros((N, pb, *arr.shape[2:]), arr.dtype)
    blocks = arr.reshape(B, nb, pb, *arr.shape[2:])
    return pool.at[table.reshape(-1)].set(
        blocks.reshape(B * nb, pb, *arr.shape[2:])
    )

k_pool, v_pool = to_pool(K), to_pool(V)
meta = qz.QuantizedKeys(to_pool(qk.codes), to_pool(qk.scale),
                        to_pool(qk.zero), g)
length = jnp.full((B,), S - 1, jnp.int32)
mesh = make_mesh((2, 2), ("data", "model"))
spec = ShardSpec(mesh=mesh, tp_axes=("model",), dp_axes=("data",))

def count(pipeline):
    pol = PolicyConfig(
        kind="fier", budget=32, group=8, skip_layers=0, sink=2, recent=4,
        pipeline=pipeline, layout="paged", block_size=bs, pool_blocks=N,
    )
    plan = DecodePlan.build(pol, shard=spec)
    return count_fn_score_bytes(
        lambda q, kp, vp: sharded_paged_decode_step(
            q, k_new, v_new, kp, vp, meta, table, length, pol, plan, spec
        )[0],
        S, q, k_pool, v_pool,
    )

ref = count("reference")
assert ref > 0, "counter is blind inside shard_map: reference counted 0"
one = count("one_pass")
assert one == 0.0, f"sharded one-pass leaked score bytes: {one}"
print("score bytes: reference", ref, "one_pass", one)
""" % (_BENCH_DIR,))


def test_sharded_chaos_audits_clean():
    """Seeded random fault schedules against a DP=2 sharded engine: the
    scheduler drains, every request retires with a structured outcome,
    and the per-shard allocators audit clean with zero leaked blocks."""
    run_in_subprocess("""
import warnings
import jax
from repro.launch.mesh import make_mesh
from repro.configs import reduced_config
from repro.core.policy import PolicyConfig
from repro.serving import (
    ContinuousScheduler, Engine, Request, ServingFaultInjector,
)

cfg = reduced_config("olmo-1b")
pol = PolicyConfig(
    kind="fier", budget=16, group=8, skip_layers=1, sink=2, recent=4,
    pipeline="reference", layout="paged", block_size=8, pool_blocks=40,
)
eng = Engine.build(cfg, n_slots=4, capacity=64, policy=pol,
                   mesh=make_mesh((2,), ("data",)))
params = eng.bundle.init(jax.random.PRNGKey(0))
reqs = [Request(rid=i, tokens=list(range(2 + i, 12 + i)), max_new=12)
        for i in range(4)]
for seed in (0, 1):
    inj = ServingFaultInjector.random(
        seed, rids=[0, 1, 2, 3], n_faults=3, step_lo=1, step_hi=8
    )
    sched = ContinuousScheduler(eng, params, injector=inj, audit_every=3)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        res = sched.run(reqs)
    assert sorted(res.outcomes) == [0, 1, 2, 3]
    assert all(o.status in ("finished", "cancelled", "quarantined",
                            "rejected") for o in res.outcomes.values()), (
        seed, {r: o.status for r, o in res.outcomes.items()})
    eng.audit()
    assert eng.allocator.n_in_use == 0, seed
    print("chaos seed", seed, "audits clean")
""")
