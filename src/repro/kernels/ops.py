"""Jit'd public wrappers around the Pallas kernels.

Handle padding to block multiples, dispatch kernel vs. pure-jnp reference
(`use_kernel=False` or unavailable platform → ref), and adapt to the
CountSketch pytree API so callers can swap paths with one flag.

On this CPU container the kernels run in interpret mode (Python-level
execution of the kernel body); on TPU the same calls compile to Mosaic.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quantize as quantize_mod
from repro.core.hashing import MulShiftParams
from repro.core.quantize import GridSpec
from repro.core.sketch import CountSketch
from repro.kernels import cic as _cic  # noqa: F401 (registers cic ops)
from repro.kernels import hash_points as _hp
from repro.kernels import ref as _ref
from repro.kernels import registry
from repro.kernels import sketch_estimate as _se
from repro.kernels import sketch_update as _su
from repro.kernels import tsne_forces as _tf  # noqa: F401 (registers tsne_step)


def _pad_to(x: jnp.ndarray, multiple: int, axis: int = 0,
            value=0) -> Tuple[jnp.ndarray, int]:
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), n


def hash_points(params: MulShiftParams, grid: GridSpec, points: jnp.ndarray,
                log2_cols: int, *, block_items: int = 1024,
                use_kernel: bool = True, interpret: bool = True
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused quantize+pack+hash.  Returns (buckets (R, N), signs (R, N))."""
    if not use_kernel:
        return _ref.hash_points(params, grid, points, log2_cols)
    padded, n = _pad_to(points, block_items, axis=0)
    b, s = _hp.hash_points(params, grid, padded, log2_cols,
                           block_items=block_items, interpret=interpret)
    return b[:, :n], s[:, :n]


def sketch_update_fused(sk: CountSketch, key_hi: jnp.ndarray,
                        key_lo: jnp.ndarray,
                        values: Optional[jnp.ndarray] = None,
                        *, block_items: int = 1024,
                        interpret: bool = True) -> CountSketch:
    """Fused hash+accumulate path (low-latency, C ≤ 2¹⁶ — see kernel doc).
    Semantics identical to ``sketch.update``."""
    if sk.cols > (1 << 16):
        raise ValueError(
            f"kernel path supports C <= 2^16 (VMEM-resident table); "
            f"got C={sk.cols}.  Use sketch.update_sorted for bulk streams.")
    n = key_hi.shape[0]
    v = jnp.ones((n,), jnp.float32) if values is None \
        else values.astype(jnp.float32)
    khi, _ = _pad_to(key_hi, block_items)
    klo, _ = _pad_to(key_lo, block_items)
    vpad, _ = _pad_to(v, block_items)          # pad value 0 → no-op updates
    delta = _su.sketch_update_table(
        sk.params, khi, klo, vpad, rows=sk.rows, log2_cols=sk.log2_cols,
        block_items=block_items, interpret=interpret)
    return sk._replace(table=sk.table + delta.astype(sk.table.dtype))


def sketch_estimate_mxu(sk: CountSketch, key_hi: jnp.ndarray,
                        key_lo: jnp.ndarray, *, block_q: int = 256,
                        block_c: int = 512, interpret: bool = True
                        ) -> jnp.ndarray:
    """MXU estimate path: median over rows of one-hot-gathered counts."""
    from repro.core import hashing, sketch as sketch_mod
    n = key_hi.shape[0]
    buckets = hashing.bucket_hash(sk.params, key_hi, key_lo, sk.log2_cols)
    signs = hashing.sign_hash(sk.params, key_hi, key_lo)
    bpad, _ = _pad_to(buckets, block_q, axis=1)
    spad, _ = _pad_to(signs, block_q, axis=1)
    est = _se.sketch_estimate_table(
        sk.table.astype(jnp.float32), bpad, spad,
        block_q=block_q, block_c=block_c, interpret=interpret)
    return jnp.median(est[:, :n], axis=0)


def cic_splat(i0: jnp.ndarray, f: jnp.ndarray, vals: jnp.ndarray,
              grid_size: int, *, block_items: Optional[int] = None,
              interpret: Optional[bool] = None,
              mode: Optional[str] = None) -> jnp.ndarray:
    """Cloud-in-cell splat of (N, C) channel masses → (C, G, G) grid.

    Pads the point list to ``block_items`` (padded rows carry zero mass,
    so they splat nothing).  Dispatch goes through ``kernels.registry``
    (op ``cic_splat``): ``mode`` forces a registry mode; the legacy
    ``interpret`` flag is a backend-derived default that a process-level
    pin (override / ``SNS_KERNEL_MODE``) beats; both-None resolves
    per backend (``registry.resolve``).  ``block_items`` None consults
    the per-backend tile table.
    """
    impl = registry.resolve("cic_splat",
                            mode=registry.legacy_mode("cic_splat",
                                                      interpret, mode),
                            shape=vals.shape, dtype=vals.dtype)
    if block_items is None:
        block_items = registry.tile_params(
            "cic_splat", shape=vals.shape)["block_items"]
    i0p, _ = _pad_to(i0, block_items)
    fp, _ = _pad_to(f, block_items)
    vp, _ = _pad_to(vals, block_items)        # pad mass 0 → no-op splat
    return impl.fn(i0p, fp, vp, grid_size, block_items=block_items)


def cic_gather(fields: jnp.ndarray, i0: jnp.ndarray, f: jnp.ndarray, *,
               block_items: Optional[int] = None,
               interpret: Optional[bool] = None,
               mode: Optional[str] = None) -> jnp.ndarray:
    """Bilinear gather of C grid fields at N points → (N, C).

    Pads the point list to ``block_items`` and slices the junk rows off.
    Dispatch as in :func:`cic_splat` (op ``cic_gather``).
    """
    impl = registry.resolve("cic_gather",
                            mode=registry.legacy_mode("cic_gather",
                                                      interpret, mode),
                            shape=fields.shape, dtype=fields.dtype)
    if block_items is None:
        block_items = registry.tile_params(
            "cic_gather", shape=fields.shape)["block_items"]
    i0p, n = _pad_to(i0, block_items)
    fp, _ = _pad_to(f, block_items)
    out = impl.fn(fields, i0p, fp, block_items=block_items)
    return out[:n]


def tsne_step_fused(x: jnp.ndarray, y: jnp.ndarray, beta: jnp.ndarray,
                    zp: jnp.ndarray, *, shift: Optional[jnp.ndarray] = None,
                    weights: Optional[jnp.ndarray] = None,
                    exaggeration=1.0, block: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    mode: Optional[str] = None,
                    return_kl: bool = False):
    """One fused tSNE gradient: pass-1 Z reduction + pass-2 force tiles.

    ``shift`` is the per-row log-domain shift paired with ``zp`` (None =
    unshifted zp, the legacy convention); ``weights`` the normalized point
    masses (None = uniform 1/N, the classic symmetrization).  Exaggeration
    may be a traced scalar.  Dispatch goes through ``kernels.registry``
    (op ``tsne_step``; ``mode``/``interpret`` as in :func:`cic_splat`,
    ``block`` None consults the tile table).  Inputs are promoted to fp32
    before the kernel regardless of dtype (fp16/bf16 in → fp32 accum).
    With ``return_kl`` also returns the KL of exag·P against current Q.
    """
    n = x.shape[0]
    impl = registry.resolve("tsne_step",
                            mode=registry.legacy_mode("tsne_step",
                                                      interpret, mode),
                            shape=x.shape, dtype=x.dtype)
    if block is None:
        block = registry.tile_params("tsne_step", shape=x.shape)["block"]
    m = jnp.zeros((n,), jnp.float32) if shift is None else shift
    w = jnp.full((n,), 1.0 / n, jnp.float32) if weights is None \
        else weights / jnp.sum(weights)
    stats = jnp.stack([beta.astype(jnp.float32), m.astype(jnp.float32),
                       zp.astype(jnp.float32), w.astype(jnp.float32)], axis=1)
    xpad, _ = _pad_to(x.astype(jnp.float32), block)
    ypad, _ = _pad_to(y.astype(jnp.float32), block)
    spad = jnp.pad(stats, [(0, (-n) % block), (0, 0)])
    # padded rows: zp=1 avoids 0-div, w=0 removes them from P
    if (-n) % block:
        spad = spad.at[n:, 2].set(1.0)
    exag = jnp.asarray(exaggeration, jnp.float32)
    f, kl_parts, z = impl.fn(xpad, ypad, spad, exag, block=block, n_valid=n)
    if not return_kl:
        return f[:n]
    kl = kl_parts[0, 0] - kl_parts[0, 1] + exag * jnp.log(z)
    return f[:n], kl
