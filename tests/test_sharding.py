"""Sharding plan: every param of every arch gets a divisible PartitionSpec
on the production meshes (AbstractMesh — no devices needed)."""
import jax
import jax.numpy as jnp
import pytest

from jax.sharding import AbstractMesh
from repro.configs import ARCHS, get_config, padded_vocab
from repro.launch.sharding import param_pspec, _path_str

MESH_1POD = AbstractMesh((16, 16), ("data", "model"))
MESH_2POD = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _axis_size(mesh, name):
    return dict(zip(mesh.axis_names, mesh.axis_sizes))[name]


@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("mesh", [MESH_1POD, MESH_2POD], ids=["1pod", "2pod"])
def test_param_specs_divisible(arch, mesh):
    from repro.models import build_model

    cfg = get_config(arch)
    bundle = build_model(cfg, max_positions=64)
    shapes = jax.eval_shape(bundle.init, jax.random.key(0))
    fsdp = ("data",)
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    assert flat, arch
    for path, leaf in flat:
        spec = param_pspec(_path_str(path), len(leaf.shape), fsdp)
        for dim, names in enumerate(spec):
            if names is None:
                continue
            names = names if isinstance(names, tuple) else (names,)
            total = 1
            for n in names:
                total *= _axis_size(mesh, n)
            assert leaf.shape[dim] % total == 0, (
                f"{arch}: {_path_str(path)} dim {dim} ({leaf.shape[dim]}) "
                f"not divisible by {names} ({total})"
            )


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_padded_vocab_divisible(arch):
    cfg = get_config(arch)
    assert padded_vocab(cfg) % 256 == 0
    assert padded_vocab(cfg) >= cfg.vocab


def test_kv_cache_seq_dims_divisible():
    """decode KV sequence sharding: 32k and 500k caches divide the shard
    counts and keep whole quantization groups per shard."""
    for S, shards in ((32_768, 16), (524_288, 256), (524_288, 512)):
        S_loc = S // shards
        assert S % shards == 0
        assert S_loc % 32 == 0, "FIER group must not straddle shards"
        assert S_loc % 8 == 0, "packing byte must not straddle shards"
