"""Distributed FIER: sequence-sharded decode vs single-device oracle."""
import jax
import jax.numpy as jnp
import numpy as np

from conftest import run_in_subprocess

_COMMON = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core import quantize as qz, retrieval as rt, distributed as dist

B, S, Hkv, Hq, D, g = 2, 256, 2, 4, 32, 8
ks = jax.random.split(jax.random.PRNGKey(0), 4)
K = jax.random.normal(ks[0], (B, S, Hkv, D)) * jnp.exp(jax.random.normal(ks[3], (D,)))
V = jax.random.normal(ks[1], (B, S, Hkv, D))
q = jax.random.normal(ks[2], (B, Hq, D))
length = jnp.array([256, 200], jnp.int32)
qk = qz.quantize(K, g)
mesh = make_mesh((4,), ("model",))
n_shards = 4
S_loc = S // n_shards

def sharded(mode, budget):
    def body(q_l, K_l, V_l, c_l, s_l, z_l, len_l):
        meta_l = qz.QuantizedKeys(c_l, s_l, z_l, g)
        start = jax.lax.axis_index("model") * S_loc
        return dist.fier_decode_sharded(
            q_l, K_l, V_l, meta_l, budget, len_l, axis=("model",),
            shard_start=start, n_shards=n_shards, mode=mode)
    kv = P(None, "model")
    f = jax.shard_map(body, mesh=mesh,
        in_specs=(P(), kv, kv, kv, kv, kv, P()), out_specs=P(), check_vma=False)
    return jax.jit(f)(q, K, V, qk.codes, qk.scale, qk.zero, length)

def full_sharded():
    def body(q_l, K_l, V_l, len_l):
        start = jax.lax.axis_index("model") * S_loc
        return dist.full_decode_sharded(q_l, K_l, V_l, len_l, axis=("model",),
                                        shard_start=start)
    kv = P(None, "model")
    f = jax.shard_map(body, mesh=mesh, in_specs=(P(), kv, kv, P()),
                          out_specs=P(), check_vma=False)
    return jax.jit(f)(q, K, V, length)
"""


def test_full_decode_sharded_equals_dense():
    run_in_subprocess(_COMMON + """
ref = rt.full_attention_decode(q, K, V, length)
got = full_sharded()
np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(ref, np.float32),
                           atol=2e-3, rtol=2e-3)
print("full sharded == dense OK")
""")


def test_exact_mode_matches_single_device_fier():
    run_in_subprocess(_COMMON + """
budget = 64
ref = rt.fier_decode_reference(q, K, V, qk, budget=budget, length=length)
got = sharded("exact", budget)
np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(ref, np.float32),
                           atol=2e-3, rtol=2e-3)
print("exact mode == single-device FIER OK")
""")


def test_local_mode_close_to_global_fier():
    """mode='local' splits the budget evenly — an approximation; its output
    must stay close to full attention when the budget is generous."""
    run_in_subprocess(_COMMON + """
budget = 128
full = rt.full_attention_decode(q, K, V, length)
got = sharded("local", budget)
err = float(jnp.abs(got.astype(jnp.float32) - full.astype(jnp.float32)).mean())
scale = float(jnp.abs(full).mean())
assert err < 0.25 * scale, (err, scale)
print("local mode close to full OK", err, scale)
""")


def test_budget_full_exact_mode_equals_dense():
    """budget = S in exact mode ⇒ every token selected ⇒ dense attention."""
    run_in_subprocess(_COMMON + """
got = sharded("exact", S)
full = rt.full_attention_decode(q, K, V, length)
np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(full, np.float32),
                           atol=2e-3, rtol=2e-3)
print("exact-full-budget == dense OK")
""")


def test_model_decode_with_seq_sharded_cache():
    """End-to-end: transformer decode_step with the cache sequence-sharded
    over a 2×2 mesh equals the unsharded decode."""
    run_in_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.configs import reduced_config
from repro.core.policy import PolicyConfig
from repro.models import build_model, DistConfig
from repro.launch import sharding as shard

cfg = reduced_config("olmo-1b")
pol = PolicyConfig(kind="fier", budget=16, group=8, skip_layers=1)
mesh = make_mesh((2, 2), ("data", "model"))

bundle_plain = build_model(cfg, pol)
# exact mode: global-top-k threshold via all-gather — must match the
# single-device policy path exactly (mode='local' is a documented
# approximation and is exercised by test_local_mode_close_to_global_fier)
dcfg = DistConfig(mesh=mesh, seq_axes=("model",), batch_axes=("data",),
                  mode="exact")
bundle_dist = build_model(cfg, pol, dcfg)

params = bundle_plain.init(jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab)
pre = {"tokens": toks, "lengths": jnp.full((2,), 32, jnp.int32)}
logits, cache = jax.jit(lambda p, b: bundle_plain.prefill(p, b, capacity=64))(params, pre)
tok = jnp.argmax(logits, -1).astype(jnp.int32)

l_plain, c_plain = jax.jit(bundle_plain.decode_step)(params, tok, cache)

baxes = shard.cache_batch_axes(bundle_dist.init_cache)
cache_sh = shard.cache_shardings(jax.eval_shape(lambda: cache), mesh, ("data",),
                                 ("model",), baxes)
cache_s = jax.tree.map(jax.device_put, cache, cache_sh)
l_dist, c_dist = jax.jit(bundle_dist.decode_step)(params, tok, cache_s)

# local mode with generous budget (16 of 64) — rankings should agree
agree = (np.argmax(np.asarray(l_plain), -1) == np.argmax(np.asarray(l_dist), -1)).mean()
assert agree == 1.0, agree
# cache contents must be IDENTICAL (append is exact regardless of mode)
for a, b in zip(jax.tree.leaves(c_plain), jax.tree.leaves(c_dist)):
    np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-2)
print("seq-sharded model decode OK")
""")
