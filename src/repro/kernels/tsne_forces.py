"""Pallas TPU kernels: flash-style fused tSNE gradient.

Exact tSNE materializes three (N, N) matrices per iteration (P, Q, and
(P−Q)·num).  At the paper's N = 2·10⁴ representatives that is 4.8 GB of
HBM traffic per iteration; beyond N ≈ 10⁵ it stops fitting entirely.  This
kernel *never materializes any N×N matrix*: like flash attention, it
streams (Bi × Bj) tiles, recomputing both the high-dim affinity P (from
the calibrated per-point statistics beta / shift / zp / w — see
``repro.core.tsne.PointStats``) and the low-dim kernel Q on the fly,
accumulating forces tile-by-tile in VMEM.

Two passes per iteration (Z is a global reduction that must precede the
force weighting — same structure as flash attention's softmax statistics):

    pass 1 (``tsne_z``):       Z = Σ_{i≠j} 1/(1+|y_i−y_j|²)
    pass 2 (``tsne_forces``):  F_i = 4 Σ_j (exag·P_ij − num_ij/Z)·num_ij·(y_i−y_j)

with the weighted symmetrization  P_ij = ½ (w_i·pc(j|i) + w_j·pc(i|j)),
pc(j|i) = exp(−beta_i·d²x_ij − shift_i)/zp_i.  Uniform w_i = 1/N recovers
the classic (pc + pcᵀ)/2N.  ``shift`` is the flash-style log-domain row
shift that keeps the recomputed exponentials in range.  Exaggeration and
Z arrive as traced scalars so the kernel can live inside a ``fori_loop``
without retracing per phase.  Pass 2 also accumulates the two KL partial
sums Σ pe·log pe and Σ pe·log num so the optimizer gets the loss for free.

Both passes are (N/B)² tile grids; all matmuls (x_i·x_jᵀ, pq·y_j) hit the
MXU.  HBM traffic drops from O(N²) to O(N²·D/B) — with B = 512, D ≤ 10,
that is a ≥ 50× reduction, turning the embedder from memory-bound to
compute-bound (see EXPERIMENTS.md §Perf for the roofline arithmetic).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import registry


def _sq_dists(a, b):
    """(Bi, D), (Bj, D) -> (Bi, Bj) squared distances, MXU-shaped."""
    a2 = jnp.sum(a * a, axis=1)
    b2 = jnp.sum(b * b, axis=1)
    cross = jnp.dot(a, b.T, preferred_element_type=jnp.float32)
    return jnp.maximum(a2[:, None] - 2.0 * cross + b2[None, :], 0.0)


def _pair_mask(bi, bj, block, n_valid):
    """(Bi, Bj) True where the pair is valid (off-diagonal, not padding)."""
    gi = bi * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    gj = bj * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    return (gi != gj) & (gi < n_valid) & (gj < n_valid)


# Scalar accumulators live in one (8, 128) f32 tile: Mosaic cannot store
# a scalar to VMEM, so each partial sum is broadcast over the tile (Z) or
# over one lane column (KL parts) and read back from row 0 afterwards.
_ACC_TILE = (8, 128)


def _z_kernel(y_i_ref, y_j_ref, z_ref, *, block: int, n_valid: int):
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _init():
        z_ref[...] = jnp.zeros_like(z_ref)

    d2 = _sq_dists(y_i_ref[...], y_j_ref[...])
    num = 1.0 / (1.0 + d2)
    mask = _pair_mask(pl.program_id(0), pl.program_id(1), block, n_valid)
    z_ref[...] += jnp.sum(jnp.where(mask, num, 0.0))


def _force_kernel(x_i_ref, x_j_ref, y_i_ref, y_j_ref, s_i_ref, s_j_ref,
                  scal_ref, out_ref, kl_ref, *, block: int, n_valid: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _init_kl():
        kl_ref[...] = jnp.zeros_like(kl_ref)

    mask = _pair_mask(pl.program_id(0), pl.program_id(1), block, n_valid)
    s_i = s_i_ref[...]                       # (Bi, 4): beta, shift, zp, w
    s_j = s_j_ref[...]                       # (Bj, 4)
    # high-dim affinity, recomputed on the fly (never stored)
    d2x = _sq_dists(x_i_ref[...], x_j_ref[...])
    pc_ij = jnp.exp(-s_i[:, 0:1] * d2x - s_i[:, 1:2]) / s_i[:, 2:3]
    pc_ji = jnp.exp(-s_j[:, 0][None, :] * d2x - s_j[:, 1][None, :]) \
        / s_j[:, 2][None, :]
    p = jnp.where(
        mask, 0.5 * (s_i[:, 3:4] * pc_ij + s_j[:, 3][None, :] * pc_ji), 0.0)
    # low-dim kernel
    y_i = y_i_ref[...]
    y_j = y_j_ref[...]
    num = 1.0 / (1.0 + _sq_dists(y_i, y_j))
    num = jnp.where(mask, num, 0.0)
    q = num / scal_ref[0, 0]
    pe = scal_ref[0, 1] * p                  # exaggerated P
    pq = (pe - q) * num
    out_ref[...] += 4.0 * (
        jnp.sum(pq, axis=1, keepdims=True) * y_i
        - jnp.dot(pq, y_j, preferred_element_type=jnp.float32))
    kl_a = jnp.sum(
        jnp.where(pe > 0, pe * jnp.log(jnp.maximum(pe, 1e-37)), 0.0))
    kl_b = jnp.sum(
        jnp.where(pe > 0, pe * jnp.log(jnp.maximum(num, 1e-37)), 0.0))
    lane = jax.lax.broadcasted_iota(jnp.int32, _ACC_TILE, 1)
    kl_ref[...] += jnp.where(lane == 0, kl_a,
                             jnp.where(lane == 1, kl_b, 0.0))


@functools.partial(jax.jit, static_argnames=("block", "n_valid", "interpret"))
def tsne_z(y: jnp.ndarray, *, block: int = 256, n_valid: int = None,
           interpret: bool = True) -> jnp.ndarray:
    """Repulsive normalizer Z.  y is (N, dims), N a multiple of block."""
    n = y.shape[0]
    n_valid = n if n_valid is None else n_valid
    assert n % block == 0
    nb = n // block
    z = pl.pallas_call(
        functools.partial(_z_kernel, block=block, n_valid=n_valid),
        grid=(nb, nb),
        in_specs=[
            pl.BlockSpec((block, y.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec((block, y.shape[1]), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec(_ACC_TILE, lambda i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(_ACC_TILE, jnp.float32),
        interpret=interpret,
    )(y, y)
    return z[0, 0]


@functools.partial(jax.jit, static_argnames=("block", "n_valid", "interpret"))
def tsne_forces(x: jnp.ndarray, y: jnp.ndarray, stats: jnp.ndarray,
                z: jnp.ndarray, exaggeration: jnp.ndarray, *,
                block: int = 256, n_valid: int = None,
                interpret: bool = True
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused tSNE gradient + KL partials.

    x (N, Dh), y (N, dims), stats (N, 4) = [beta, shift, zp, w] columns,
    z / exaggeration traced scalars.  N must be a multiple of ``block``
    (ops.py pads; padded rows carry w = 0, produce zero force, and are
    masked out of every pair).

    Returns (forces (N, dims), kl_parts (1, 2)) with
    kl_parts = [Σ pe·log pe, Σ pe·log num] over valid pairs, pe = exag·P.
    """
    n = x.shape[0]
    n_valid = n if n_valid is None else n_valid
    assert n % block == 0
    nb = n // block
    scal = jnp.stack([z.astype(jnp.float32),
                      jnp.asarray(exaggeration, jnp.float32)]).reshape(1, 2)

    forces, kl = pl.pallas_call(
        functools.partial(_force_kernel, block=block, n_valid=n_valid),
        grid=(nb, nb),
        in_specs=[
            pl.BlockSpec((block, x.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec((block, x.shape[1]), lambda i, j: (j, 0)),
            pl.BlockSpec((block, y.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec((block, y.shape[1]), lambda i, j: (j, 0)),
            pl.BlockSpec((block, 4), lambda i, j: (i, 0)),
            pl.BlockSpec((block, 4), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 2), lambda i, j: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((block, y.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec(_ACC_TILE, lambda i, j: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n, y.shape[1]), jnp.float32),
            jax.ShapeDtypeStruct(_ACC_TILE, jnp.float32),
        ),
        interpret=interpret,
    )(x, x, y, y, stats, stats, scal)
    return forces, kl[0:1, 0:2]


# -- XLA reference + registry wiring ----------------------------------------
# The registered op "tsne_step" is the full two-pass iteration on the
# PADDED arrays: fn(x, y, stats, exaggeration, *, block, n_valid) ->
# (forces (N, dims), kl_parts (1, 2), z).  ops.tsne_step_fused handles
# padding/unpadding and routes here through the registry.

def tsne_step_xla(x: jnp.ndarray, y: jnp.ndarray, stats: jnp.ndarray,
                  exaggeration, *, block: int = 256,
                  n_valid: int = None) -> Tuple[jnp.ndarray, jnp.ndarray,
                                                jnp.ndarray]:
    """Dense pure-jnp reference with the kernel's exact masking and KL
    partial-sum semantics (``block`` is accepted and ignored)."""
    n = x.shape[0]
    n_valid = n if n_valid is None else n_valid
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    beta, shift, zp, w = (stats[:, 0].astype(jnp.float32),
                          stats[:, 1].astype(jnp.float32),
                          stats[:, 2].astype(jnp.float32),
                          stats[:, 3].astype(jnp.float32))
    idx = jnp.arange(n)
    mask = ((idx[:, None] != idx[None, :])
            & (idx[:, None] < n_valid) & (idx[None, :] < n_valid))
    d2x = _sq_dists(x, x)
    pc = jnp.exp(-beta[:, None] * d2x - shift[:, None]) / zp[:, None]
    p = jnp.where(mask, 0.5 * (w[:, None] * pc + w[None, :] * pc.T), 0.0)
    num = jnp.where(mask, 1.0 / (1.0 + _sq_dists(y, y)), 0.0)
    z = jnp.sum(num)
    exag = jnp.asarray(exaggeration, jnp.float32)
    pe = exag * p
    pq = (pe - num / z) * num
    forces = 4.0 * (jnp.sum(pq, axis=1, keepdims=True) * y
                    - jnp.dot(pq, y, preferred_element_type=jnp.float32))
    kl_parts = jnp.stack([
        jnp.sum(jnp.where(pe > 0, pe * jnp.log(jnp.maximum(pe, 1e-37)), 0.0)),
        jnp.sum(jnp.where(pe > 0, pe * jnp.log(jnp.maximum(num, 1e-37)),
                          0.0))]).reshape(1, 2)
    return forces, kl_parts, z


#: Largest tile the compiled force kernel runs.  Its (B, B) f32
#: temporaries overflow the 16 MiB scoped VMEM above B = 128 (B = 256
#: needs a raised limit and ~50 s of compile; B = 512 does not finish
#: compiling for ``v5e``), so larger requested blocks that are multiples
#: of it run as 128-row tiles over the same padded arrays.
COMPILED_MAX_BLOCK = 128


def _step_mode(interpret: bool):
    def fn(x, y, stats, exaggeration, *, block: int = 256, n_valid=None):
        if not interpret and block % COMPILED_MAX_BLOCK == 0:
            block = COMPILED_MAX_BLOCK
        z = tsne_z(y, block=block, n_valid=n_valid, interpret=interpret)
        f, kl_parts = tsne_forces(
            x, y, stats, z, jnp.asarray(exaggeration, jnp.float32),
            block=block, n_valid=n_valid, interpret=interpret)
        return f, kl_parts, z
    return fn


registry.register("tsne_step", "compiled")(_step_mode(False))
registry.register("tsne_step", "interpret")(_step_mode(True))
registry.register("tsne_step", "xla")(tsne_step_xla)
