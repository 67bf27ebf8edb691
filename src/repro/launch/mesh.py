"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run must
set XLA_FLAGS before any jax initialization.

Axis semantics:
  "pod"   — across TPU pods / data centers (DCN links, ~25 GB/s/host).
            Only sketch merges and gradient reductions cross it.
  "data"  — data parallel + FSDP shard axis inside a pod (ICI).
  "model" — tensor/expert parallel axis (ICI).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np


def make_mesh(shape: Tuple[int, ...], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules and
    ``with_sharding_constraint`` calls in this stack name mesh axes in
    ``NamedSharding`` specs, which ``Explicit`` axes (the installed
    JAX's default) refuse."""
    axes = tuple(axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape: Tuple[int, ...], axes: Sequence[str]):
    """Arbitrary small mesh for tests/examples on host devices."""
    return make_mesh(shape, axes)


def dp_axes(mesh) -> Tuple[str, ...]:
    """Data-parallel axes of a mesh = every axis that is not 'model'."""
    return tuple(a for a in mesh.axis_names if a != "model")


def dp_size(mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in dp_axes(mesh)]))


def tp_size(mesh) -> int:
    return int(mesh.shape["model"]) if "model" in mesh.axis_names else 1
