"""Random weights from ``--seed``, made on the device in one jitted call,
in the program's parameter layout and the type it serves them in (f32);
and the per-layer view the plain reference reads them through.

This module is the one place that knows the program's parameter layout.
Scales follow the usual fan-in init: matrices N(0, 1/fan_in), the
embedding N(0, 1/d_model).  The layout is OLMo's: no norm parameters, no
biases, a tied LM head.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole ``seed``, also past 32 bits."""
    key = jax.random.PRNGKey(0)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, seed >> 64):
        key = jax.random.fold_in(key, jnp.uint32(word & 0xFFFFFFFF))
    return key


def _leaf(names: tuple[str, ...], sds, key) -> jax.Array:
    shape, dtype = sds.shape, sds.dtype
    noise = jax.random.normal(key, shape, jnp.float32)
    if names[-1] == "embed":
        x = noise * shape[-1] ** -0.5
    elif len(shape) >= 2:                   # a matrix [..., fan_in, fan_out]
        x = noise * shape[-2] ** -0.5
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


def layer_view(params, layer: int) -> dict:
    """Layer ``layer`` under the reference's names."""
    lp = jax.tree.map(lambda a: a[layer], params["layers"])
    attn, mlp = lp["attn"], lp["mlp"]
    return {"wq": attn["wq"], "wk": attn["wk"], "wv": attn["wv"], "wo": attn["wo"],
            "w_gate": mlp["w1"], "w_up": mlp["w3"], "w_down": mlp["w2"]}


def embed(params, vocab: int) -> jax.Array:
    """The [vocab, d] embedding (the program pads its rows), which is also
    the tied LM head."""
    if "lm_head" in params:
        raise ValueError("the program's head is not tied to its embedding")
    return params["embed"][:vocab]
