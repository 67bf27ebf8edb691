"""Pallas TPU kernel: tiled squared-distance scan for the approximate kNN
candidate stage.

The ann candidate generator (``core.ann``) turns the bucketing pass into a
regular computation: after sorting points by grid-cell key, each sorted
tile of B query rows scores the same shared window of C = 3B candidate
rows (its own tile plus a one-tile halo on each side).  That is exactly an
MXU-shaped block — one (B, D) × (D, C) matmul per tile plus rank-1
row/column norm corrections:

    d²(q, c) = |q|² + |c|² − 2·q@cᵀ

The kernel computes one (B, C) block of squared distances per grid step
and masks invalid candidates to +inf in-register:

* ``cid < 0``   — window padding (halo beyond the sorted range, or tile
  padding past N);
* ``cid == qid`` — self-pairs.

``top_k`` selection stays outside in XLA (per-row k-selection is not MXU
work).  ``distance_tiles`` dispatches between the kernel (its compiled
tier on an accelerator, the interpreter on CPU) and the pure-XLA
reference (``tile="xla"``), and tests pin fp agreement between the two,
padding paths included.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import registry


def _dist_kernel(qx_ref, qid_ref, cx_ref, cid_ref, out_ref):
    q = qx_ref[0]                                            # (B, D)
    c = cx_ref[0]                                            # (C, D)
    qid = qid_ref[0]                                         # (B, 1)
    cid = cid_ref[0]                                         # (1, C)
    qq = jnp.sum(q * q, axis=1)
    cc = jnp.sum(c * c, axis=1)
    cross = jnp.dot(q, c.T, preferred_element_type=jnp.float32)
    d2 = jnp.maximum(qq[:, None] + cc[None, :] - 2.0 * cross, 0.0)
    invalid = (cid < 0) | (cid == qid)
    out_ref[0] = jnp.where(invalid, jnp.inf, d2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _distance_tiles_pallas(qx: jnp.ndarray, qid: jnp.ndarray,
                           cx: jnp.ndarray, cid: jnp.ndarray,
                           interpret: bool = True) -> jnp.ndarray:
    t, b, d = qx.shape
    c = cx.shape[1]
    # ids ride as a (B, 1) column and a (1, C) row: 3-D blocks whose last
    # two dims equal the array's satisfy the TPU (8, 128) tiling rule
    return pl.pallas_call(
        _dist_kernel,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, b, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, b, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, c, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, c), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, b, c), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, b, c), jnp.float32),
        interpret=interpret,
    )(qx.astype(jnp.float32), qid[:, :, None], cx.astype(jnp.float32),
      cid[:, None, :])


@jax.jit
def _distance_tiles_xla(qx: jnp.ndarray, qid: jnp.ndarray,
                        cx: jnp.ndarray, cid: jnp.ndarray) -> jnp.ndarray:
    qx = qx.astype(jnp.float32)
    cx = cx.astype(jnp.float32)
    qq = jnp.sum(qx * qx, axis=2)                            # (T, B)
    cc = jnp.sum(cx * cx, axis=2)                            # (T, C)
    cross = jnp.einsum("tbd,tcd->tbc", qx, cx,
                       preferred_element_type=jnp.float32)
    d2 = jnp.maximum(qq[:, :, None] + cc[:, None, :] - 2.0 * cross, 0.0)
    invalid = (cid[:, None, :] < 0) | (cid[:, None, :] == qid[:, :, None])
    return jnp.where(invalid, jnp.inf, d2)


def distance_tiles(qx: jnp.ndarray, qid: jnp.ndarray, cx: jnp.ndarray,
                   cid: jnp.ndarray, *, tile: str = "xla",
                   interpret: Optional[bool] = None,
                   mode: Optional[str] = None) -> jnp.ndarray:
    """Masked squared-distance blocks for T query tiles.

    qx (T, B, D) query rows, qid (T, B) int32 global ids, cx (T, C, D)
    candidate windows, cid (T, C) int32 candidate ids (−1 = padding).
    Returns (T, B, C) float32 squared distances with padding and
    self-pairs forced to +inf.

    Dispatch goes through ``kernels.registry`` (op ``knn_dist_tiles``).
    ``mode`` forces a registry mode directly; with ``mode=None`` a
    process-level pin (``SNS_KERNEL_MODE`` / override) wins, else
    ``tile="xla"`` takes the XLA reference and ``tile="pallas"`` the
    kernel: an explicit ``interpret`` bool picks its tier, None lets the
    registry resolve it (compiled on an accelerator, interpret on CPU).
    """
    if tile not in ("pallas", "xla"):
        raise ValueError(f"unknown distance tile backend: {tile!r}")
    if mode is None:
        pinned = registry.resolve_mode(None, "knn_dist_tiles")
        if pinned != "auto":
            mode = pinned
        elif tile == "pallas":
            mode = registry.coerce_mode(interpret, None)
        else:
            mode = "xla"
    impl = registry.resolve("knn_dist_tiles", mode=mode, shape=qx.shape,
                            dtype=qx.dtype)
    return impl.fn(qx, qid, cx, cid)


registry.register("knn_dist_tiles", "compiled")(
    lambda qx, qid, cx, cid: _distance_tiles_pallas(
        qx, qid, cx, cid, interpret=False))
registry.register("knn_dist_tiles", "interpret")(
    lambda qx, qid, cx, cid: _distance_tiles_pallas(
        qx, qid, cx, cid, interpret=True))
registry.register("knn_dist_tiles", "xla")(_distance_tiles_xla)
