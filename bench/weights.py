"""Random weights from ``--seed``, made on the device in one jitted call,
in the program's parameter layout and the type it serves them in.

The rules follow the program's layout alone: a leaf under ``layers``
carries a leading layer axis, and the rest is one layer's shape.
Matrices are drawn N(0, 1/fan_in), the embedding N(0, 1/d_model), and a
vector (a norm's gain) 1 + 0.1·N(0, 1), so that a reference that leaves
a gain out reads a different model.  Which leaf a reference reads as
what is the configuration's architecture module's (``layer_view``,
``head_view`` in ``bench/arch/<config>.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

GAIN_NOISE = 0.1


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole ``seed``, also past 32 bits."""
    key = jax.random.PRNGKey(0)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, seed >> 64):
        key = jax.random.fold_in(key, jnp.uint32(word & 0xFFFFFFFF))
    return key


def _leaf(names: tuple[str, ...], sds, key) -> jax.Array:
    shape, dtype = sds.shape, sds.dtype
    one = shape[1:] if names[0] == "layers" else shape     # one layer's shape
    noise = jax.random.normal(key, shape, jnp.float32)
    if names[-1] == "embed":
        x = noise * shape[-1] ** -0.5
    elif len(one) >= 2:                     # a matrix [..., fan_in, fan_out]
        x = noise * shape[-2] ** -0.5
    elif len(one) == 1:
        x = 1.0 + GAIN_NOISE * noise
    else:
        raise ValueError(f"no rule for the parameter {'/'.join(names)} {shape}")
    return x.astype(dtype)


def make(shapes, seed: int):
    """Weights shaped like ``shapes`` (``jax.eval_shape`` of the program's
    init), drawn from ``seed`` in one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [tuple(str(getattr(k, "key", getattr(k, "name", k))) for k in path)
             for path, _ in flat]

    @jax.jit
    def draw(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _leaf(n, sds, jax.random.fold_in(key, i))
            for i, (n, (_, sds)) in enumerate(zip(names, flat))
        ])

    return draw(seed_key(seed))


def nbytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))
