"""Production meshes.  Functions, not module constants — importing this
module never touches jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* first init).

Topology: TPU v5e, 16×16 = 256 chips per pod; 2 pods = 512 chips over DCN.
Axis meanings:
    pod    — data parallel across pods (gradient all-reduce over DCN)
    data   — data parallel / FSDP within a pod
    model  — tensor/expert parallel + decode-time KV sequence sharding

Every mesh in the repo is built through :func:`make_mesh`, which pins
``AxisType.Auto`` on all axes: the code places arrays with
``NamedSharding`` / ``with_sharding_constraint`` and lets GSPMD propagate
the rest (``jax.make_mesh`` alone defaults to ``Explicit`` axes, whose
sharding-in-types rules the model code was not written for).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Whatever devices exist (tests / single host): (data, model)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return make_mesh((n // model_axis, model_axis), ("data", "model"))


def batch_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def fsdp_axes(mesh, param_bytes: float) -> tuple[str, ...]:
    """FSDP policy: everything shards over 'data'; >50 GB param trees also
    shard over 'pod' (ZeRO-3 across pods, paid in inter-pod all-gathers)."""
    if param_bytes > 50e9 and "pod" in mesh.axis_names:
        return ("pod", "data")
    return ("data",)
