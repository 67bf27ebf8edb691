"""Pallas TPU kernels: cloud-in-cell splat/gather for the FFT repulsion grid.

The sparse tSNE backend's repulsion pass moves all N points through a G×G
particle-mesh grid every iteration (``tsne.fft_repulsion``): splat the
masses (1, y_x, y_y) bilinearly onto the grid, FFT-convolve, gather the
fields back bilinearly.  The XLA path expresses the splat as four
scatter-adds of N updates each — fine on CPU at moderate N, but scatter
is the one primitive in the sparse iteration that does not vectorize.

These kernels recast BOTH directions as dense one-hot matmuls, which is
what the MXU actually wants:

* for a tile of B points, build the separable bilinear weight matrices
  wx, wy (B, G) — each row holds (1−f) at the point's cell and f at
  cell+1, so the outer product wx[p]ᵀ·wy[p] is exactly the 4-corner CIC
  stencil;
* splat:   grid[c]  = Σ_p m_c[p]·wx[p]ᵀ·wy[p]  →  (wxᵀ∘m_c) @ wy,
  accumulated across point tiles (the grid output block is revisited by
  every step of the 1-D point grid);
* gather:  out[p,c] = wx[p] @ field[c] @ wy[p]ᵀ  →  rowsum((wx@field[c])∘wy).

Cost per tile and channel is one (G, B)×(B, G) (splat) or (B, G)×(G, G)
(gather) matmul — O(G²) MACs per point, MORE flops than the 4-corner
stencil's O(1) updates, but they are dense MXU flops instead of XLA's
serial scatter-update walk; the trade only pays where scatter stalls the
pipeline (keep G moderate — at the adaptive cap G = 1024 the one-hot
matrices dwarf the stencil work even on the MXU).  On CPU the kernels run
in interpret mode (tests pin fp agreement against the XLA path); dispatch
is via ``TsneConfig.cic = "pallas"`` through ``tsne.fft_repulsion``.

Padding contract (handled by ``ops.cic_splat``/``ops.cic_gather``): point
tiles are padded to ``block_items``; padded rows carry zero masses (splat
adds nothing) and their gathered rows are sliced off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import registry

# The splat keeps its whole (C, G, G) accumulator resident in VMEM.  At the
# adaptive-grid cap G = 1024 that is 12 MiB, over the 16 MiB default
# scoped limit once the one-hot temporaries are added; 64 MiB (half of a
# v5e core's VMEM) compiles for ``v5e`` (tests/test_tpu_compile.py).
_SPLAT_VMEM_LIMIT = 64 << 20


def _onehot_weights(i0: jnp.ndarray, f: jnp.ndarray, g: int):
    """Separable CIC weight matrices wx, wy (B, G) for one point tile."""
    b = i0.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (b, g), 1)
    wx = jnp.where(iota == i0[:, 0:1], 1.0 - f[:, 0:1], 0.0) \
        + jnp.where(iota == i0[:, 0:1] + 1, f[:, 0:1], 0.0)
    wy = jnp.where(iota == i0[:, 1:2], 1.0 - f[:, 1:2], 0.0) \
        + jnp.where(iota == i0[:, 1:2] + 1, f[:, 1:2], 0.0)
    return wx, wy


def _splat_kernel(i0_ref, f_ref, vals_ref, out_ref, *, g: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    wx, wy = _onehot_weights(i0_ref[...], f_ref[...], g)
    vals = vals_ref[...]                                     # (B, C)
    for c in range(out_ref.shape[0]):
        out_ref[c] += jnp.dot(wx.T * vals[:, c][None, :], wy,
                              preferred_element_type=jnp.float32)


def _gather_kernel(fields_ref, i0_ref, f_ref, out_ref, *, g: int):
    wx, wy = _onehot_weights(i0_ref[...], f_ref[...], g)
    for c in range(fields_ref.shape[0]):
        tmp = jnp.dot(wx, fields_ref[c],
                      preferred_element_type=jnp.float32)    # (B, G)
        out_ref[:, c] = jnp.sum(tmp * wy, axis=1)


@functools.partial(jax.jit,
                   static_argnames=("grid_size", "block_items", "interpret"))
def cic_splat(i0: jnp.ndarray, f: jnp.ndarray, vals: jnp.ndarray,
              grid_size: int, *, block_items: int = 1024,
              interpret: bool = True) -> jnp.ndarray:
    """Splat per-point channel masses onto the grid: (C, G, G).

    i0 (N, 2) int32 cell indices in [0, G−2], f (N, 2) fractional
    offsets, vals (N, C) channel masses (zero rows = padding no-ops).
    N must be a multiple of ``block_items`` (ops.py pads).
    """
    n, c = vals.shape
    assert n % block_items == 0
    return pl.pallas_call(
        functools.partial(_splat_kernel, g=grid_size),
        grid=(n // block_items,),
        in_specs=[
            pl.BlockSpec((block_items, 2), lambda i: (i, 0)),
            pl.BlockSpec((block_items, 2), lambda i: (i, 0)),
            pl.BlockSpec((block_items, c), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((c, grid_size, grid_size),
                               lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((c, grid_size, grid_size),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_SPLAT_VMEM_LIMIT),
        interpret=interpret,
    )(i0, f.astype(jnp.float32), vals.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("block_items", "interpret"))
def cic_gather(fields: jnp.ndarray, i0: jnp.ndarray, f: jnp.ndarray, *,
               block_items: int = 1024, interpret: bool = True
               ) -> jnp.ndarray:
    """Bilinear per-point gather of C grid fields: (N, C).

    fields (C, G, G) float32, i0/f as in :func:`cic_splat`.  N must be a
    multiple of ``block_items`` (ops.py pads; padded rows are junk to be
    sliced off by the caller).
    """
    c, g, _ = fields.shape
    n = i0.shape[0]
    assert n % block_items == 0
    return pl.pallas_call(
        functools.partial(_gather_kernel, g=g),
        grid=(n // block_items,),
        in_specs=[
            pl.BlockSpec((c, g, g), lambda i: (0, 0, 0)),
            pl.BlockSpec((block_items, 2), lambda i: (i, 0)),
            pl.BlockSpec((block_items, 2), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_items, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, c), jnp.float32),
        interpret=interpret,
    )(fields.astype(jnp.float32), i0, f.astype(jnp.float32))


# -- XLA references + registry wiring ---------------------------------------

def cic_splat_xla(i0: jnp.ndarray, f: jnp.ndarray, vals: jnp.ndarray,
                  grid_size: int, **_tile) -> jnp.ndarray:
    """Pure-XLA splat: four scatter-adds, one per CIC corner.  Same
    padding contract as the kernel (zero-mass rows splat nothing)."""
    f = f.astype(jnp.float32)
    v = vals.astype(jnp.float32)
    out = jnp.zeros((vals.shape[1], grid_size, grid_size), jnp.float32)
    for dx in (0, 1):
        for dy in (0, 1):
            w = ((f[:, 0] if dx else 1.0 - f[:, 0])
                 * (f[:, 1] if dy else 1.0 - f[:, 1]))      # (N,)
            out = out.at[:, i0[:, 0] + dx, i0[:, 1] + dy].add(
                w[None, :] * v.T)
    return out


def cic_gather_xla(fields: jnp.ndarray, i0: jnp.ndarray, f: jnp.ndarray,
                   **_tile) -> jnp.ndarray:
    """Pure-XLA gather: four corner gathers, bilinearly weighted."""
    f = f.astype(jnp.float32)
    fld = fields.astype(jnp.float32)
    acc = jnp.zeros((i0.shape[0], fields.shape[0]), jnp.float32)
    for dx in (0, 1):
        for dy in (0, 1):
            w = ((f[:, 0] if dx else 1.0 - f[:, 0])
                 * (f[:, 1] if dy else 1.0 - f[:, 1]))      # (N,)
            acc = acc + w[:, None] * fld[:, i0[:, 0] + dx, i0[:, 1] + dy].T
    return acc


def _splat_mode(interpret: bool):
    def fn(i0, f, vals, grid_size, *, block_items: int = 1024):
        return cic_splat(i0, f, vals, grid_size, block_items=block_items,
                         interpret=interpret)
    return fn


def _gather_mode(interpret: bool):
    def fn(fields, i0, f, *, block_items: int = 1024):
        return cic_gather(fields, i0, f, block_items=block_items,
                          interpret=interpret)
    return fn


registry.register("cic_splat", "compiled")(_splat_mode(False))
registry.register("cic_splat", "interpret")(_splat_mode(True))
registry.register("cic_splat", "xla")(cic_splat_xla)
registry.register("cic_gather", "compiled")(_gather_mode(False))
registry.register("cic_gather", "interpret")(_gather_mode(True))
registry.register("cic_gather", "xla")(cic_gather_xla)
