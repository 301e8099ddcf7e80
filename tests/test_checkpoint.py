"""Checkpoint manager: roundtrip, async, GC, elastic mesh resharding."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager

from conftest import run_in_subprocess


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 16)), "b": jnp.zeros((16,))},
        "opt": {"mu": jnp.ones((8, 16)), "step": jnp.int32(7)},
    }


def test_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    st = _state()
    mgr.save(3, st)
    assert mgr.latest_step() == 3
    back = mgr.restore(3, st)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_n=2)
    st = _state()
    for step in (1, 2, 3, 4):
        mgr.save_async(step, st)
    mgr.wait()
    assert mgr.all_steps() == [3, 4]


def test_atomic_publish_no_tmp_left(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _state())
    assert not [d for d in os.listdir(tmp_path) if d.endswith(".tmp")]


def test_tree_mismatch_rejected(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _state())
    with pytest.raises(ValueError):
        mgr.restore(1, {"different": jnp.zeros(3)})


def test_elastic_reshard_between_meshes():
    """Save under mesh (4,) sharding, restore onto mesh (2,) — the elastic
    path after losing half the slice."""
    run_in_subprocess("""
import tempfile
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.checkpoint import CheckpointManager

d = tempfile.mkdtemp()
mgr = CheckpointManager(d)
x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
mesh4 = make_mesh((4,), ("data",))
x4 = jax.device_put(x, NamedSharding(mesh4, P("data")))
mgr.save(5, {"x": x4})

mesh2 = make_mesh((2,), ("data",))
sh2 = {"x": NamedSharding(mesh2, P("data"))}
back = mgr.restore(5, {"x": x}, sharding=sh2)
np.testing.assert_array_equal(np.asarray(back["x"]), np.asarray(x))
assert back["x"].sharding.mesh.shape["data"] == 2
print("elastic reshard OK")
""")
