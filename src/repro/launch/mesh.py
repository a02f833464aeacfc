"""Production mesh construction (assignment deliverable e).

make_production_mesh is a FUNCTION — importing this module never touches
jax device state.  Single pod: (data=16, model=16) over 256 chips.
Multi-pod: (pod=2, data=16, model=16) over 512 chips; the `pod` axis is a
second data-parallel axis crossing the slower inter-pod links (gradient
all-reduce over it can be int8-compressed, optim.grad_compress).
"""
from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def abstract_mesh(shape, axes):
    """Device-less mesh for sharding-spec legality checks."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(axes))


def make_mesh_from_devices(devices, shape, axes):
    """Mesh over an explicit device subset (elastic re-mesh after node
    loss, or the single-pod 256-of-512 slice in the dry-run)."""
    arr = np.asarray(devices).reshape(shape)
    return jax.sharding.Mesh(arr, axes)


def single_pod_mesh_from(devices):
    """16x16 (data, model) mesh from the first 256 of the given devices."""
    return make_mesh_from_devices(list(devices)[:256], (16, 16),
                                  ("data", "model"))


def row_mesh(devices=None, axis: str = "rows"):
    """1-D mesh over `devices` (default: all) for row-sharded batch
    evaluation — the sweep engine splits its flattened (GEMM, config,
    mapping) row batches over this axis (repro.core.sweep).

    `jax.devices()` is the GLOBAL device list, so in a multi-process
    jax.distributed job the default mesh already spans every host; the
    engine then routes evaluation through the multi-host path
    (launch.distributed: per-host shard materialization + output
    all-gather).  Pass `jax.local_devices()` to force a one-host mesh."""
    devices = list(devices if devices is not None else jax.devices())
    return make_mesh_from_devices(devices, (len(devices),), (axis,))


def small_mesh(n_data: int = 1, n_model: int = 1):
    """Tiny mesh for CPU tests (devices must already exist)."""
    devs = jax.devices()[: n_data * n_model]
    return make_mesh_from_devices(devs, (n_data, n_model),
                                  ("data", "model"))
