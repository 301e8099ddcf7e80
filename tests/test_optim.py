"""Optimizer substrate: AdamW, schedules, 1-bit gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import (
    adamw_init,
    adamw_update,
    clip_by_global_norm,
    compress_decompress,
    cosine_schedule,
    ef_state_init,
    wsd_schedule,
)

from conftest import run_in_subprocess


def test_adamw_minimises_quadratic():
    params = {"x": jnp.array([5.0, -3.0])}
    opt = adamw_init(params)
    loss = lambda p: jnp.sum(p["x"] ** 2)
    for _ in range(200):
        g = jax.grad(loss)(params)
        params, opt = adamw_update(g, opt, params, lr=0.05, weight_decay=0.0)
    assert float(loss(params)) < 1e-2


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, gn = clip_by_global_norm(g, 1.0)
    assert float(gn) == pytest.approx(20.0)
    assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0, rel=1e-5)


def test_schedules():
    assert float(cosine_schedule(0, peak_lr=1.0, warmup=10, total=100)) == 0.0
    assert float(cosine_schedule(10, peak_lr=1.0, warmup=10, total=100)) == pytest.approx(1.0)
    assert float(cosine_schedule(100, peak_lr=1.0, warmup=10, total=100)) == pytest.approx(0.1)
    # WSD: flat plateau then sharp tail
    assert float(wsd_schedule(50, peak_lr=1.0, warmup=10, total=100)) == 1.0
    assert float(wsd_schedule(89, peak_lr=1.0, warmup=10, total=100)) == 1.0
    assert float(wsd_schedule(100, peak_lr=1.0, warmup=10, total=100)) == pytest.approx(0.01)


def test_error_feedback_accumulates_residual():
    g = {"w": jnp.array([1.0, -0.1, 0.05, -2.0])}
    ef = ef_state_init(g)
    comp, ef = compress_decompress(g, ef)
    scale = float(jnp.mean(jnp.abs(g["w"])))
    np.testing.assert_allclose(
        np.asarray(comp["w"]), scale * np.sign(np.asarray(g["w"])), rtol=1e-6
    )
    # residual carries the quantization error to the next step
    np.testing.assert_allclose(
        np.asarray(ef["w"]), np.asarray(g["w"]) - np.asarray(comp["w"]), rtol=1e-6
    )


def test_compressed_training_still_converges():
    """signSGD-with-EF through AdamW still minimises a least-squares."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    A = jax.random.normal(k1, (32, 8))
    b = jax.random.normal(k2, (32,))
    params = {"w": jnp.zeros((8,))}
    opt = adamw_init(params)
    ef = ef_state_init(params)
    loss = lambda p: jnp.mean((A @ p["w"] - b) ** 2)
    w_star, *_ = jnp.linalg.lstsq(A, b)
    l_opt = float(jnp.mean((A @ w_star - b) ** 2))
    l0 = float(loss(params))
    for _ in range(300):
        g = jax.grad(loss)(params)
        g, ef = compress_decompress(g, ef)
        params, opt = adamw_update(g, opt, params, lr=0.02, weight_decay=0.0)
    # close most of the gap to the least-squares optimum despite 1-bit grads
    assert float(loss(params)) - l_opt < 0.3 * (l0 - l_opt)


def test_compressed_psum_multidevice():
    """shard_map compressed all-reduce: mean of per-shard sign·scale."""
    run_in_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.optim import compressed_psum

mesh = make_mesh((4,), ("data",))
x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))

def body(xl):
    return compressed_psum(xl[0], "data")

f = jax.shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P(),
                  check_vma=False)
got = jax.jit(f)(x)
want = np.mean([np.sign(np.asarray(x[i])) * np.abs(np.asarray(x[i])).mean()
                for i in range(4)], axis=0)
np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
print("compressed psum OK")
""")
