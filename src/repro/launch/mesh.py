"""Production meshes.

``make_production_mesh`` is a FUNCTION (importing this module never
touches jax device state): single-pod (16, 16) = (data, model) — one v5e
pod slice of 256 chips — or multi-pod (2, 16, 16) = (pod, data, model),
512 chips.  The dry-run launcher forces 512 host platform devices before
any jax import; real launches get real device topologies.
"""
from __future__ import annotations

import jax
import numpy as np


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: GSPMD propagates the
    shardings this repo places, nothing is explicit-sharded."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_serve_mesh(data: int, model: int):
    """A (data, model) serving mesh over the first ``data*model`` devices.

    Serving meshes are allowed to occupy a *subset* of the host's devices
    (``jax.make_mesh`` wants the full set), so this reshapes an explicit
    device slice: 'data' carries the data-parallel slot-group replicas,
    'model' the tensor-parallel shards within each replica.
    """
    data, model = int(data), int(model)
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1, got ({data}, {model})")
    devs = jax.devices()
    need = data * model
    if need > len(devs):
        raise ValueError(
            f"mesh ({data}, {model}) needs {need} devices, "
            f"host has {len(devs)}")
    arr = np.asarray(devs[:need]).reshape(data, model)
    return jax.sharding.Mesh(arr, ("data", "model"))


def n_data_replicas(mesh) -> int:
    """Number of data-parallel replicas (product of the non-'model'
    batch axes): the serve pool's slot dim splits into this many groups."""
    n = 1
    for a in data_axes(mesh):
        n *= mesh.shape[a]
    return n


def make_host_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist (tests / CPU runs)."""
    devs = jax.devices()
    n = len(devs)
    mp = model_parallel
    while n % mp:
        mp -= 1
    arr = np.asarray(devs).reshape(n // mp, mp)
    return jax.sharding.Mesh(arr, ("data", "model"))


def data_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def batch_axes(mesh):
    """The PartitionSpec entry for a global-batch dimension."""
    ax = data_axes(mesh)
    return ax if len(ax) > 1 else (ax[0] if ax else None)
