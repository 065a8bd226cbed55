"""Production mesh builders. Functions, not module constants — importing this
module never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """The one mesh builder: every axis `AxisType.Auto`. `jax.make_mesh`
    defaults to Explicit axes, under which host-side indexing of a sharded
    state raises `ShardingTypeError` and shard_map bodies see typed
    sharding; the store and its routing are written for Auto axes.
    `devices` (optional) pins the mesh to given devices, e.g.
    `jax.devices()[:1]` for a one-shard engine."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=None if devices is None else list(devices))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips (16, 16) ("data", "model").
    Multi-pod: 2 pods = 512 chips (2, 16, 16) ("pod", "data", "model") —
    the pod axis is the DCI (slow) hop; routing and gradient exchange treat
    it hierarchically (coarsest first), per the paper's NUMA hierarchy."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_axes_of(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def n_devices(mesh) -> int:
    n = 1
    for a in mesh.axis_names:
        n *= mesh.shape[a]
    return n
