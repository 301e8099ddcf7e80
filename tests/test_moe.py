"""MoE: dense-scatter reference vs shard_map EP; the served drop-free
layer at a chip's share and its ``held_experts_swiglu`` kernel."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.kernels import held_experts
from repro.kernels import ref as ref_kernels
from repro.models import moe as moe_mod

from conftest import run_in_subprocess


def _setup(T=64, seed=0):
    cfg = reduced_config("granite-moe-1b-a400m")  # 4 experts, top-2, d=64
    rng = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(rng)
    p = moe_mod.init_moe(k1, cfg)
    x = jax.random.normal(k2, (T, cfg.d_model), jnp.float32)
    return cfg, p, x


def test_held_layer_matches_scatter_no_drops():
    """With every expert held and capacity_factor high enough that nothing
    drops, the served drop-free layer equals the training scatter path."""
    import dataclasses

    cfg, p, x = _setup(T=32)
    cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    y1, _ = moe_mod.moe_apply(x, p, cfg)
    y2, counts = moe_mod.moe_held(x, p, cfg)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4, rtol=1e-4)
    # every route lands on a held expert
    assert counts.tolist() == [32 * cfg.topk_experts, cfg.n_experts]


def test_aux_loss_balanced_router_is_one():
    """A perfectly uniform router gives aux ≈ 1 (Switch normalisation)."""
    cfg, p, x = _setup(T=512)
    p = dict(p, router=jnp.zeros_like(p["router"]))
    _, aux = moe_mod.moe_apply(x, p, dataclasses.replace(cfg, capacity_factor=8.0))
    assert 0.9 < float(aux) < 1.1


# ------------------------------------------------- experts at a chip's share

def _qwen_tiny(**kw):
    """Qwen3-MoE's block at a CPU size: 16 experts, top-4."""
    from repro.configs import get_config

    return dataclasses.replace(
        get_config("qwen3-moe-235b-a22b"), n_layers=2, d_model=256, n_heads=8,
        n_kv_heads=2, d_head=32, d_ff=128, vocab=512, n_experts=16, topk_experts=4,
        **kw)


def _share(p, first, held):
    return dict(p, **{n: p[n][first:first + held] for n in ("w1", "w3", "w2")})


@pytest.mark.parametrize("T", [5, 64])
def test_shares_sum_to_the_whole_layer(T):
    """The four shares of 4 experts each add up to the uncut layer, and a
    share is exactly what the layer computes for the routes it holds."""
    whole = _qwen_tiny()
    p = moe_mod.init_moe(jax.random.PRNGKey(3), whole)
    x = jax.random.normal(jax.random.PRNGKey(4), (T, whole.d_model), jnp.float32)
    y_whole, c_whole = moe_mod.moe_held(x, p, whole)
    parts, routes = [], 0
    for first in range(0, 16, 4):
        cfg = dataclasses.replace(whole, expert_first=first, experts_held=4)
        y, c = moe_mod.moe_held(x, _share(p, first, 4), cfg)
        parts.append(np.asarray(y))
        routes += int(c[0])
    np.testing.assert_allclose(sum(parts), np.asarray(y_whole), atol=1e-5, rtol=1e-5)
    assert routes == int(c_whole[0]) == T * whole.topk_experts
    # the uncut layer against its plain definition
    gates, _ = moe_mod.route(x, p["router"], whole)
    ref = ref_kernels.held_experts_swiglu(x, gates, p["w1"], p["w3"], p["w2"])
    np.testing.assert_allclose(np.asarray(y_whole), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_router_renormalises_the_top_k():
    cfg = _qwen_tiny()
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (9, cfg.d_model), jnp.float32)
    gates, routes = moe_mod.route(x, p["router"], cfg)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-6)
    assert (np.asarray(routes).sum(-1) == cfg.topk_experts).all()
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    top = np.sort(np.asarray(probs), -1)[:, -cfg.topk_experts:]
    np.testing.assert_allclose(np.sort(np.asarray(gates), -1)[:, -cfg.topk_experts:],
                               top / top.sum(-1, keepdims=True), rtol=1e-5)


def test_a_token_is_the_same_alone_and_in_a_chunk():
    """Drop-free: a token's expert output does not depend on the tokens
    that share its batch or chunk."""
    cfg = _qwen_tiny(expert_first=4, experts_held=4)
    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), moe_mod.init_moe(jax.random.PRNGKey(5), cfg))
    kx = jax.random.split(jax.random.PRNGKey(6), 2)
    chunk = jax.random.normal(kx[0], (600, cfg.d_model)).astype(jnp.bfloat16)
    other = jax.random.normal(kx[1], (600, cfg.d_model)).astype(jnp.bfloat16)
    y_chunk, _ = moe_mod.moe_held(chunk, p, cfg)
    for i in (0, 123, 599):
        y_alone, _ = moe_mod.moe_held(chunk[i:i + 1], p, cfg)
        np.testing.assert_array_equal(np.asarray(y_alone[0]), np.asarray(y_chunk[i]))
        # the same chunk shape with every other token replaced
        mixed = other.at[i].set(chunk[i])
        y_mixed, _ = moe_mod.moe_held(mixed, p, cfg)
        np.testing.assert_array_equal(np.asarray(y_mixed[i]), np.asarray(y_chunk[i]))


def _kernel_case(T, E, d=256, ff=512, seed=0, idle=(1,)):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (T, d)).astype(jnp.bfloat16)
    w1 = (jax.random.normal(k[1], (E, d, ff)) * d**-0.5).astype(jnp.bfloat16)
    w3 = (jax.random.normal(k[2], (E, d, ff)) * d**-0.5).astype(jnp.bfloat16)
    w2 = (jax.random.normal(k[3], (E, ff, d)) * ff**-0.5).astype(jnp.bfloat16)
    routed = jax.random.uniform(k[5], (T, E)) < 0.3
    g = jnp.where(routed, jax.random.uniform(k[4], (T, E)), 0.0)
    for e in idle:
        g = g.at[:, e].set(0.0)
    return x, g, w1, w3, w2


@pytest.mark.parametrize("T,E,idle", [(8, 4, (1,)), (20, 4, (0, 3)), (600, 3, (2,)),
                                      (8, 4, (0, 1, 2, 3))])
def test_held_experts_kernel_matches_its_oracle(T, E, idle):
    """Interpret mode against the jnp oracle, with held experts that no
    token routes to (skipped, their weights unread) among them."""
    x, g, w1, w3, w2 = _kernel_case(T, E, idle=idle)
    y = held_experts.held_experts_swiglu(x, g, w1, w3, w2, interpret=True)
    r = ref_kernels.held_experts_swiglu(x, g, w1, w3, w2)
    assert y.shape == (T, x.shape[1]) and y.dtype == jnp.float32
    # the kernel rounds the gated hidden to bf16 before the down projection
    np.testing.assert_allclose(np.asarray(y), np.asarray(r), atol=2e-2, rtol=2e-2)
    if len(idle) == E:
        assert not np.asarray(y).any()


def test_skipped_steps_hold_the_blocks_already_fetched():
    """A skipped (tile, expert) step points its weights at the last active
    step before it, or the first after it, so no weight block moves."""
    active = jnp.array([0, 0, 1, 0, 1, 1, 0, 0], bool)
    assert held_experts._fetch_targets(active).tolist() == [2, 2, 2, 2, 4, 5, 5, 5]
    assert held_experts._fetch_targets(jnp.zeros(4, bool)).tolist() == [0, 0, 0, 0]


def test_ep_matches_scatter_multidevice():
    """shard_map EP on a 2×2 mesh == single-device scatter reference."""
    run_in_subprocess(
        """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs import reduced_config
from repro.models import moe as moe_mod

cfg = dataclasses.replace(reduced_config("granite-moe-1b-a400m"), capacity_factor=8.0)
k1, k2 = jax.random.split(jax.random.PRNGKey(0))
p = moe_mod.init_moe(k1, cfg)
x = jax.random.normal(k2, (64, cfg.d_model), jnp.float32)
y_ref, aux_ref = moe_mod.moe_apply(x, p, cfg)

mesh = make_mesh((2, 2), ("data", "model"))
y_ep, aux_ep = jax.jit(lambda x, p: moe_mod.moe_apply_ep(
    x, p, cfg, mesh=mesh, token_axes=("data",), model_axis="model"))(x, p)
np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_ep), atol=2e-4, rtol=2e-4)
# aux is a per-shard estimator under EP (E[f·P] over shards != global f·P):
# outputs must match exactly, aux only approximately
np.testing.assert_allclose(float(aux_ref), float(aux_ep), rtol=0.05)
print("EP == scatter OK")
""",
        n_devices=4,
    )


def test_ep_with_fsdp_gather_multidevice():
    """EP with FSDP-stored expert weights (gather inside the body)."""
    run_in_subprocess(
        """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.configs import reduced_config
from repro.models import moe as moe_mod

cfg = dataclasses.replace(reduced_config("granite-moe-1b-a400m"), capacity_factor=8.0)
k1, k2 = jax.random.split(jax.random.PRNGKey(1))
p = moe_mod.init_moe(k1, cfg)
x = jax.random.normal(k2, (64, cfg.d_model), jnp.float32)
y_ref, _ = moe_mod.moe_apply(x, p, cfg)

mesh = make_mesh((2, 2), ("data", "model"))
sh = {
    "router": NamedSharding(mesh, P()),
    "w1": NamedSharding(mesh, P("model", "data", None)),
    "w3": NamedSharding(mesh, P("model", "data", None)),
    "w2": NamedSharding(mesh, P("model", None, "data")),
}
p_sharded = {k: jax.device_put(v, sh[k]) for k, v in p.items()}
x_sh = jax.device_put(x, NamedSharding(mesh, P("data", None)))
y_ep, _ = jax.jit(lambda x, p: moe_mod.moe_apply_ep(
    x, p, cfg, mesh=mesh, token_axes=("data",), model_axis="model",
    fsdp_axes=("data",)))(x_sh, p_sharded)
np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_ep), atol=2e-4, rtol=2e-4)
print("EP+FSDP == scatter OK")
""",
        n_devices=4,
    )


def test_ep_gradients_flow():
    """EP path is differentiable (psum/all_gather transpose correctly)."""
    run_in_subprocess(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs import reduced_config
from repro.models import moe as moe_mod

cfg = reduced_config("granite-moe-1b-a400m")
k1, k2 = jax.random.split(jax.random.PRNGKey(0))
p = moe_mod.init_moe(k1, cfg)
x = jax.random.normal(k2, (64, cfg.d_model), jnp.float32)
mesh = make_mesh((2, 2), ("data", "model"))

def loss(p, x):
    y, aux = moe_mod.moe_apply_ep(x, p, cfg, mesh=mesh, token_axes=("data",),
                                  model_axis="model")
    return jnp.sum(y * y) + 0.01 * aux

g = jax.jit(jax.grad(loss))(p, x)
for k, v in g.items():
    assert bool(jnp.isfinite(v).all()), k
assert float(jnp.abs(g["w1"]).sum()) > 0
print("EP grads OK")
""",
        n_devices=4,
    )


@pytest.mark.parametrize("held", [16, 4])
def test_route_counters_of_a_served_run(held):
    """The expert layer's two counters reach the registry from the decode
    step's own readback: with every expert held, each decode step counts
    slots x top-k x layers routes."""
    from repro.obs import Observability
    from repro.serving import ContinuousScheduler, Engine, Request
    from repro.serving.engine import serving_policy

    cfg = dataclasses.replace(_qwen_tiny(), experts_held=held, expert_first=16 - held)
    pol = dataclasses.replace(
        serving_policy(budget=64, group=32, skip_layers=1, sink=4, recent=16,
                       pipeline="one_pass", layout="paged"), block_size=32)
    eng = Engine.build(cfg, n_slots=2, capacity=256, policy=pol, layout="paged",
                       block_size=32, obs=Observability())
    params = eng.bundle.init(jax.random.PRNGKey(0))
    sched = ContinuousScheduler(eng, params, chunk_tokens=64)
    rng = np.random.default_rng(0)
    sched.run([Request(rid=i, tokens=rng.integers(0, 512, size=n).tolist(), max_new=6)
               for i, n in enumerate((100, 150))])
    snap = eng.obs.metrics.snapshot()
    routes = snap.value("moe_held_routes_total")
    touched = snap.value("moe_held_experts_touched_total")
    per_step = 2 * cfg.topk_experts * cfg.n_layers
    assert 0 < touched <= routes <= sched.steps * per_step
    assert touched <= sched.steps * held * cfg.n_layers
    if held == cfg.n_experts:
        assert routes == sched.steps * per_step
