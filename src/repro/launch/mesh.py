"""Device meshes — the repo's one mesh constructor and the named meshes
built from it.

Functions (not module constants) so importing this module never touches jax
device state — the dry-run must set XLA_FLAGS before first jax init.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> Mesh:
    """A mesh whose axes are all ``Auto``.  The sharding rules place
    activations with ``with_sharding_constraint``, which refuses the
    ``Explicit`` axes that ``jax.make_mesh`` defaults to; every mesh in the
    repo therefore comes from here.  ``devices`` pins the mesh to those
    devices (e.g. one replica per chip); by default all devices are used."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """1-device mesh for CPU tests (axis names match production)."""
    return make_mesh((1, 1), ("data", "model"))


def make_serving_mesh(data: Optional[int] = None, model: int = 1, *,
                      devices: Optional[Sequence] = None) -> Mesh:
    """``(data, model)`` mesh for the sharded diffusion serving engine —
    slots over ``data``, DiT weights tensor-parallel over ``model``.
    ``data`` defaults to ``len(devices) // model``; ``devices`` defaults
    to all devices."""
    n = len(devices) if devices is not None else jax.device_count()
    if data is None:
        data = max(1, n // model)
    if data * model > n:
        raise ValueError(f"mesh ({data}, {model}) needs {data * model} "
                         f"devices, have {n}")
    if devices is not None:
        devices = list(devices)[:data * model]
    return make_mesh((data, model), ("data", "model"), devices=devices)
