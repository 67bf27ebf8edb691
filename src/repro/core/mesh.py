"""Shared device-mesh plumbing for every shard_map stage.

The ingest stage has run inside ``shard_map`` since PR 2 (``core.geo``:
per-device sketching with hierarchical ``psum`` merge); the embed stage
joined it in this PR (``core.tsne``/``core.umap``: row-block-sharded
iteration loops).  This module hoists the pieces both sides need so no
stage carries its own copy:

* :func:`shard_map_compat` — ``jax.shard_map`` with replication checks
  off, the one spelling every stage uses;
* :func:`make_embed_mesh` / :func:`resolve_mesh` — build or normalize the
  1-D embed mesh ``SnsConfig.embed_mesh`` names (``None`` | device count |
  a ready ``Mesh``);
* :func:`linear_index` — the traced linear shard id inside a shard_map
  body (the idiom ``geo.geo_extract_from_shards`` open-coded);
* :func:`axis_size` / :func:`row_block` — static sizing helpers for
  row-block sharding: each device owns a contiguous, equal-size (padded)
  row range, the layout every sharded embed reduction builds on.

Collective contract of the sharded embed stage (enforced by jaxpr
regressions in tests/test_mesh_embed.py): per-device bodies communicate
ONLY through ``psum`` of fixed-size partials (the CIC grid, dst-side
per-block reductions, KL terms) and ``all_gather`` of the row-block
positions — no cross-device scatter anywhere, mirroring the paper's
"only fixed-size summaries move" discipline at the embed layer.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P  # noqa: F401 (re-export)

# the 1-D mesh axis the sharded embed stage runs over
EMBED_AXIS = "embed"


def shard_map_compat(*, mesh, in_specs, out_specs):
    """Decorator: ``jax.shard_map`` with replication checks off."""
    return functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)


def make_embed_mesh(n_devices: Optional[int] = None,
                    axis: str = EMBED_AXIS) -> Mesh:
    """A 1-D mesh over the first ``n_devices`` local devices (all by
    default), one row block per device — the topology the
    row-block-sharded embed stage runs on."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if not 1 <= n <= len(devs):
        raise ValueError(
            f"embed mesh wants {n} devices; {len(devs)} available")
    return Mesh(np.asarray(devs[:n]), (axis,))


def resolve_mesh(spec: Union[None, int, Mesh],
                 axis: str = EMBED_AXIS) -> Optional[Mesh]:
    """Normalize ``SnsConfig.embed_mesh``: ``None`` stays single-device, an
    int builds a fresh 1-D mesh of that many devices, a ``Mesh`` passes
    through as-is (its first axis is the embed axis)."""
    if spec is None:
        return None
    if isinstance(spec, Mesh):
        return spec
    if isinstance(spec, int):
        return make_embed_mesh(spec, axis=axis)
    raise TypeError(
        f"embed_mesh must be None, a device count, or a Mesh; got {spec!r}")


def mesh_axis(mesh: Mesh) -> str:
    """The (single) axis name of a 1-D embed mesh."""
    return mesh.axis_names[0]


def axis_size(mesh: Mesh, axes: Union[str, Sequence[str]]) -> int:
    """Total device count along one axis or a sequence of axes."""
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def linear_index(mesh: Mesh, axes: Union[str, Sequence[str]]) -> jnp.ndarray:
    """Traced linear shard id inside a ``shard_map`` body, row-major over
    ``axes`` (the idiom previously open-coded in
    ``geo.geo_extract_from_shards``)."""
    if isinstance(axes, str):
        axes = (axes,)
    idx = jnp.zeros((), jnp.int32)
    for a in axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def row_block(n: int, n_shards: int) -> Tuple[int, int]:
    """Row-block sizing for sharding ``n`` rows over ``n_shards`` devices:
    returns (rows_per_shard, n_padded) with ``n_padded = rows_per_shard ·
    n_shards ≥ n`` — device s owns global rows
    [s·rows_per_shard, (s+1)·rows_per_shard), the tail rows are padding."""
    rows_per = -(-n // n_shards)
    return rows_per, rows_per * n_shards
