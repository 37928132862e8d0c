"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state; the dry-run pins the 512-placeholder-device env
var before any jax import.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``.

    Since jax 0.9 a bare ``jax.make_mesh`` gives ``Explicit`` axes, under
    which the models' embedding gathers raise ``ShardingTypeError``; every
    mesh in the repo is built here so the partitioner keeps deciding the
    layouts."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def kernel_mesh():
    """1-D ``batch`` mesh over every local device, for sharding the crypto
    kernels' element batches (``core.paillier_batch._shard_batch``).

    Returns ``None`` on single-device hosts — the common CPU container —
    so callers can skip the device_put entirely.  Multi-chip hosts (or a
    CPU with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``) get
    every chip working on a slice of the batch: the limb ops are
    batch-elementwise, so partitioning the leading axis shards the whole
    ladder with zero cross-device traffic until the caller gathers.
    """
    n = jax.local_device_count()
    if n <= 1:
        return None
    return make_mesh((n,), ("batch",))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod; the multi-pod mesh adds a leading 2-pod axis.

    Axes: `data` carries FSDP + batch sharding, `model` carries TP/EP;
    `pod` (multi-pod) carries pure DP — parameters stay pod-replicated and
    gradients all-reduce across (pod, data).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_shape_dict(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_axes(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
