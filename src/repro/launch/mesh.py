"""Production mesh definitions (TPU v5e pods).

Single pod: (data=16, model=16) = 256 chips.
Multi-pod:  (pod=2, data=16, model=16) = 512 chips — the `pod` axis
composes with `data` for batch/gradient parallelism; model parallelism
never crosses the pod boundary (DCN-friendly).

Functions, not module constants: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).

Every mesh is built with ``AxisType.Auto`` axes: the jitted bodies are
traced without a mesh in context and leave partitioning to the
compiler, which ``jax.make_mesh``'s ``Explicit`` default does not allow.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model: int = 1, devices: Optional[Sequence] = None):
    """Degenerate mesh on the real local device(s) — tests/examples.

    ``devices`` (default: all of ``jax.devices()``) lets several meshes
    split one host's devices — see :func:`replica_meshes`.

    ``model=1`` is the common fast path (the serving tests' 1-device
    equivalence oracle): every local device lands on ``data`` without
    consulting divisibility at all.  Any other ``model`` must divide
    the device count exactly — a remainder used to silently build
    a mesh over ``(n // model) * model < n`` devices, which then failed
    far away inside jit with an opaque sharding error.
    """
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    if model == 1:
        return _mesh((n, 1), ("data", "model"), devices)
    if model < 1 or n % model != 0:
        raise ValueError(
            f"make_host_mesh: model={model} must be >= 1 and divide "
            f"the device count {n} exactly (got remainder "
            f"{n % model if model >= 1 else model}); pick a model-axis "
            f"size from the divisors of {n}")
    return _mesh((n // model, model), ("data", "model"), devices)


def replica_meshes(n_replicas: int, model: int = 1) -> list:
    """One host mesh per engine replica, on disjoint devices.

    The local devices split into ``n_replicas`` equal contiguous groups
    (a remainder stays idle), and each group becomes a
    ``(len // model, model)`` mesh, so replicas never share a chip.
    With fewer devices than replicas, replica ``r`` takes device
    ``r % n`` alone: replicas then share devices round-robin.
    """
    if n_replicas < 1:
        raise ValueError(f"n_replicas={n_replicas} must be >= 1")
    devs = jax.devices()
    per = len(devs) // n_replicas
    if per == 0:
        return [make_host_mesh(model, [devs[r % len(devs)]])
                for r in range(n_replicas)]
    return [make_host_mesh(model, devs[r * per:(r + 1) * per])
            for r in range(n_replicas)]


def batch_axes(mesh) -> tuple:
    """Mesh axes that shard the batch dimension."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
