"""Production mesh construction + sharding-rule tables.

Mesh (assignment-fixed): single pod = (16, 16) over ("data", "model");
multi-pod = (2, 16, 16) over ("pod", "data", "model"), pod axis = pure DP.
"""
from __future__ import annotations

import os

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, found {len(devices)} — the dry-run entrypoint "
            "must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import (see launch/dryrun.py)")
    dev_array = np.asarray(devices[:n]).reshape(shape)
    return Mesh(dev_array, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")) -> Mesh:
    n = int(np.prod(shape))
    return Mesh(np.asarray(jax.devices()[:n]).reshape(shape), axes)


def make_shard_mesh(n_shards: int | None = None, axis: str = "shard") -> Mesh:
    """1-D mesh for the sharded peel substrate (``GraphSpec.shard_axis``).

    ``n_shards=None`` takes every visible device — the usual way to turn a
    ``--xla_force_host_platform_device_count=N`` run (or a TPU slice) into
    a truss engine mesh.
    """
    devices = jax.devices()
    n = len(devices) if n_shards is None else int(n_shards)
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, found {len(devices)}")
    return Mesh(np.asarray(devices[:n]), (axis,))


def emulated_devices_env(n_devices: int, **extra: str) -> dict:
    """Environment for a child process that emulates ``n_devices`` host
    devices.  ``JAX_PLATFORMS=cpu`` pins the child to the CPU: an
    accelerator serves one process, so a child that inherited its parent's
    backend would contend with the parent for the chip."""
    return dict(os.environ, JAX_PLATFORMS="cpu",
                XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}",
                **extra)


def dp_axes(mesh: Mesh):
    """The combined pure-data-parallel axes of a mesh."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def model_size(mesh: Mesh) -> int:
    return mesh.shape["model"]


def named(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
