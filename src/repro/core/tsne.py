"""tSNE in pure JAX — the paper's downstream embedder, three backends.

Faithful to van der Maaten & Hinton 2008 + the reference implementation:

* per-point perplexity calibration by binary search over sigma (fixed 50
  iterations, vectorized over points, streamed in row blocks),
* symmetrized joint P, early exaggeration, momentum + per-parameter gains,
* exact gradient  4·Σ_j (p_ij − q_ij)(y_i − y_j)/(1 + |y_i − y_j|²).

Weighted extension (SnS): each input point carries a weight w_i (the HH
count).  P is built from the weighted conditional probabilities, so a
representative standing for 10⁶ raw points pulls proportionally harder —
this is the "replication" of paper §II-1 done in closed form (replicas
are still supported; weights are the numerically-clean equivalent).

Calibration never materializes an (N, N) matrix: it streams row blocks
(``lax.map`` over chunks) and returns per-point sufficient statistics
``PointStats`` — precision beta, a log-domain row shift, the shifted row
normalizer zp, and the normalized point mass w.  Every backend rebuilds
P_ij = ½(w_i·pc(j|i) + w_j·pc(i|j)) from these four numbers per point,
flash-attention style.

Gradient backends (``TsneConfig.backend`` / ``run_tsne(backend=...)``):

========== ============== ================= =====================================
backend    per-iter time  per-iter memory   when to use
========== ============== ================= =====================================
``dense``  O(N²)          O(N²)             N ≤ 2·10⁴ (paper regime); exact
``tiled``  O(N²)          O(block·N)        N ≤ ~10⁵: exact, bounded memory
``pallas`` O(N²)          O(block²) VMEM    TPU: exact, fused two-pass kernel
``sparse`` O(N·k+G²logG)  O(N·k + G²)       N = 10⁵–10⁶: kNN attraction + FFT
                                            grid repulsion (BH/FIt-SNE style)
========== ============== ================= =====================================

``dense``/``tiled``/``pallas`` compute the exact gradient and agree to fp
tolerance (tests/test_embed_backends.py).  ``sparse`` is the sub-quadratic
approximation: attraction restricted to the symmetrized kNN graph
(perplexity calibrated against kNN distances only — van der Maaten 2014),
repulsion via cloud-in-cell splatting onto a G×G grid in embedding space,
one FFT convolution with the (1+r²)⁻¹/(1+r²)⁻² kernels, and a bilinear
gather back — the Z normalizer falls out of the same grid pass
(FIt-SNE, Linderman et al. 2019).  On a complete kNN graph (k = N−1) its
attraction term equals the dense one exactly; repulsion converges to the
exact field as G grows (tests/test_sparse_tsne.py).

Two further sparse-backend knobs (this PR's follow-ups to the above):

* adaptive grid — ``grid_interval > 0`` fixes the target CELL SPACING
  instead of the grid size: the optimizer runs in jitted stages and G
  doubles (grid_size → grid_max) whenever the embedding span outgrows
  the spacing, FIt-SNE-style, retracing only at doubling boundaries;
* ``cic="pallas"`` — the cloud-in-cell splat/gather runs as the one-hot
  matmul Pallas tile in ``repro.kernels.cic`` (compiled for the MXU on
  an accelerator, the Pallas interpreter on CPU) instead of the XLA
  scatter/gather loop.

The per-edge attraction reduction goes through the shared sorted-COO
core (:mod:`repro.core.coo`) — the same scatter-free machinery the UMAP
epoch loop uses.

Mesh-parallel sparse backend (``run_tsne(mesh=...)`` — ``None`` | device
count | 1-D ``Mesh``, plumbing in :mod:`repro.core.mesh`): the whole
optimizer loop runs inside ``shard_map``, each device owning a
contiguous row block of the state and the matching contiguous slice of
the src-sorted COO edges (``coo.ShardedEdgeLayout``, built host-side at
setup).  Attraction stays a local ``segment_reduce``; repulsion splats
per-device masses and ``psum``s the tiny (3, G, G) grid; per iteration
the only collectives are one ``all_gather`` of the block positions plus
fixed-size psums (grid, Z, KL, centering) — no cross-device scatter
(jaxpr-pinned in tests/test_mesh_embed.py).  Per-iteration quantities
match the single-device path to fp tolerance; long trajectories
decohere, as any summation-order change must under a chaotic optimizer.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import coo
from repro.core import mesh as mesh_mod
from repro.kernels import registry as registry_mod

BACKENDS = ("dense", "tiled", "pallas", "sparse")
CIC_PATHS = ("xla", "pallas")


@dataclasses.dataclass(frozen=True)
class TsneConfig:
    dims: int = 2
    perplexity: float = 30.0
    n_iter: int = 500
    early_exaggeration: float = 12.0
    exaggeration_iters: int = 125
    learning_rate: float = 200.0
    momentum_start: float = 0.5
    momentum_final: float = 0.8
    momentum_switch: int = 125
    min_gain: float = 0.01
    sigma_search_iters: int = 50
    backend: str = "dense"         # "dense" | "tiled" | "pallas" | "sparse"
    block: int = 512               # row-block for calibration / tiled / pallas
    knn: int = 0                   # sparse: neighbors per point (0 → 3·perp)
    grid_size: int = 128           # sparse: FFT repulsion grid, G per axis
    # adaptive grid (FIt-SNE-style): > 0 turns grid_size into the STARTING
    # G and fixes the target cell spacing in embedding units — the grid
    # doubles (up to grid_max) whenever the embedding span outgrows it,
    # re-jitting only at the doubling boundaries (staged optimizer)
    grid_interval: float = 0.0     # 0 = fixed-G; > 0 = target cell spacing
    grid_max: int = 1024           # adaptive: G cap (bounds the FFT cost)
    adaptive_interval: int = 50    # adaptive: iterations between G checks
    cic: str = "xla"               # grid splat/gather: "xla" | "pallas"
    # kNN build for the sparse backend: "exact" | "auto" | "ann" — "auto"
    # switches to the approximate engine (core.ann) above
    # AnnConfig.auto_threshold points; ``ann`` carries its knobs (an
    # ann.AnnConfig — hashable, so the config stays jit-static)
    knn_method: str = "auto"
    ann: Optional[object] = None
    # kernel dispatch mode for every Pallas call site (CIC splat/gather,
    # fused force tile, segment reduce), via kernels.registry: "auto"
    # takes the registry's per-backend pick (never the interpreter on an
    # accelerator); the other values force one mode end-to-end
    # (SnsConfig.kernel_mode threads to here)
    kernel_mode: str = "auto"


class PointStats(NamedTuple):
    """Per-point sufficient statistics for rebuilding P on the fly.

    pc(j|i) = exp(−beta_i·d²(x_i, x_j) − shift_i) / zp_i   (0 on the diag),
    P_ij    = ½ (w_i·pc(j|i) + w_j·pc(i|j)),   Σ_ij P_ij = 1.

    ``shift`` is the per-row max logit (flash-style log-domain shift) so zp
    never under/overflows regardless of the calibrated precision.
    """
    beta: jnp.ndarray    # (N,) precision 1/(2 sigma²)
    shift: jnp.ndarray   # (N,) row max of −beta_i·d², subtracted pre-exp
    zp: jnp.ndarray      # (N,) shifted row normalizer Σ_{j≠i} exp(logit−shift)
    w: jnp.ndarray       # (N,) normalized point mass, Σ w = 1


def validate_init(init, n: int, dims: int) -> Optional[jnp.ndarray]:
    """Shape/dtype-check a warm-start embedding init (shared by both
    embedders).  Accepts None (cold start) or an (N, dims) float array;
    returns it as float32 or raises with the offending shape/dtype."""
    if init is None:
        return None
    init = jnp.asarray(init)
    if init.shape != (n, dims):
        raise ValueError(
            f"init must have shape ({n}, {dims}) to seed the embedding; "
            f"got {init.shape}")
    if not jnp.issubdtype(init.dtype, jnp.floating):
        raise ValueError(f"init must be a float array; got {init.dtype}")
    return init.astype(jnp.float32)


def pairwise_sq_dists(x: jnp.ndarray, y: Optional[jnp.ndarray] = None
                      ) -> jnp.ndarray:
    """Squared Euclidean distances via the Gram-matrix identity (MXU-shaped)."""
    y = x if y is None else y
    xx = jnp.sum(x * x, axis=1)
    yy = jnp.sum(y * y, axis=1)
    d = xx[:, None] - 2.0 * (x @ y.T) + yy[None, :]
    return jnp.maximum(d, 0.0)


def _pad_rows(x: jnp.ndarray, block: int, value=0) -> jnp.ndarray:
    pad = (-x.shape[0]) % block
    if pad == 0:
        return x
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=value)


def _rows_probs_entropy(neg_d: jnp.ndarray, beta: jnp.ndarray
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Row-wise conditional P and Shannon entropy for precision beta.

    neg_d: (B, N) negative squared distances, −inf at invalid pairs.
    """
    logits = neg_d * beta[:, None]
    logits = logits - jnp.max(logits, axis=1, keepdims=True)
    p = jnp.exp(logits)
    p_sum = jnp.sum(p, axis=1, keepdims=True)
    p = p / p_sum
    logp = logits - jnp.log(p_sum)
    h = -jnp.sum(jnp.where(p > 0, p * logp, 0.0), axis=1)
    return p, h


def _beta_search(neg_d: jnp.ndarray, target_h: jnp.ndarray,
                 search_iters: int) -> jnp.ndarray:
    """Per-row binary search for beta = 1/(2σ²) matching the target entropy.

    neg_d: (B, M) negative squared distances, −inf at invalid pairs.
    Fixed iteration count → jit-compatible; identical bisection on the
    full row (dense calibration) and the kNN row (sparse calibration).
    """
    nrows = neg_d.shape[0]

    def body(_, state):
        beta, lo, hi = state
        _, h = _rows_probs_entropy(neg_d, beta)
        too_entropic = h > target_h             # entropy high -> raise beta
        lo = jnp.where(too_entropic, beta, lo)
        hi = jnp.where(too_entropic, hi, beta)
        nxt = jnp.where(jnp.isinf(hi), beta * 2.0, 0.5 * (lo + hi))
        return nxt, lo, hi

    init = (jnp.ones((nrows,)), jnp.zeros((nrows,)),
            jnp.full((nrows,), jnp.inf))
    beta, _, _ = jax.lax.fori_loop(0, search_iters, body, init)
    return beta


def calibrate_stats(x: jnp.ndarray, perplexity: float,
                    weights: Optional[jnp.ndarray] = None,
                    search_iters: int = 50, block: int = 512) -> PointStats:
    """Perplexity calibration in row blocks — peak memory O(block · N).

    Binary search on beta = 1/(2 sigma²) per row (fixed iteration count,
    jit-compatible), streamed over row chunks with ``lax.map`` so no
    (N, N) buffer ever exists.
    """
    n = x.shape[0]
    block = min(block, n) if n > 0 else block
    xp = _pad_rows(x, block)
    nb = xp.shape[0] // block
    row_ids = jnp.arange(xp.shape[0])
    col_ids = jnp.arange(n)
    target_h = jnp.log(perplexity)

    def chunk_stats(args):
        xc, idc = args                              # (B, D), (B,)
        d2 = pairwise_sq_dists(xc, x)               # (B, N) — the only big temp
        valid = idc[:, None] != col_ids[None, :]
        neg_d = jnp.where(valid, -d2, -jnp.inf)
        beta = _beta_search(neg_d, target_h, search_iters)
        logits = jnp.where(valid, -d2 * beta[:, None], -jnp.inf)
        shift = jnp.max(logits, axis=1)
        zp = jnp.sum(jnp.exp(logits - shift[:, None]), axis=1)
        return beta, shift, zp

    beta, shift, zp = jax.lax.map(
        chunk_stats, (xp.reshape(nb, block, -1), row_ids.reshape(nb, block)))
    beta = beta.reshape(-1)[:n]
    shift = shift.reshape(-1)[:n]
    zp = zp.reshape(-1)[:n]
    if weights is not None:
        w = weights / jnp.sum(weights)
    else:
        w = jnp.full((n,), 1.0 / n)
    return PointStats(beta=beta, shift=shift, zp=zp, w=w)


def p_from_stats(x: jnp.ndarray, stats: PointStats) -> jnp.ndarray:
    """Dense joint P from per-point stats (the O(N²) reconstruction)."""
    n = x.shape[0]
    d2 = pairwise_sq_dists(x)
    pc = jnp.exp(-stats.beta[:, None] * d2 - stats.shift[:, None]) \
        / stats.zp[:, None]
    pc = pc.at[jnp.arange(n), jnp.arange(n)].set(0.0)
    wpc = stats.w[:, None] * pc
    p = 0.5 * (wpc + wpc.T)
    p = p / jnp.sum(p)
    return jnp.maximum(p, 1e-12)


def calibrate_p(x: jnp.ndarray, perplexity: float,
                weights: Optional[jnp.ndarray] = None,
                search_iters: int = 50, block: int = 512) -> jnp.ndarray:
    """Joint symmetrized P with per-point sigma matched to the perplexity.

    Convenience wrapper (dense result) over the blocked ``calibrate_stats``.
    """
    stats = calibrate_stats(x, perplexity, weights=weights,
                            search_iters=search_iters, block=block)
    return p_from_stats(x, stats)


# --------------------------------------------------------------------------
# Sparse backend: kNN-restricted attraction + FFT grid repulsion.
#
# Per-iteration cost O(N·k + G²·log G) instead of O(N²):
#   grad_i = 4 [ Σ_j P_ij·num_ij·(y_i−y_j)  −  (1/Z)·Σ_j num²_ij·(y_i−y_j) ]
# with num_ij = (1+|y_i−y_j|²)⁻¹.  The first sum runs over the symmetrized
# kNN support only (gather + sorted-row segment reduction over fixed-shape
# COO edges); the
# second is an all-pairs sum of a smooth radial kernel, evaluated by
# splatting unit masses (and y-weighted masses) onto a G×G grid,
# convolving with (1+r²)⁻² via FFT, and gathering back bilinearly.  The
# normalizer Z = Σ_{i≠j} num_ij comes from the same grid pass with the
# (1+r²)⁻¹ kernel.
# --------------------------------------------------------------------------

class SparseP(NamedTuple):
    """Symmetrized joint P on the kNN support, fixed-shape COO.

    ``val`` sums to exactly 1 by construction: each directed kNN edge
    (i→j) with conditional mass c_ij = w_i·pc(j|i) contributes c_ij/2 to
    the ordered pairs (i, j) AND (j, i), so after folding duplicates the
    entry for (i, j) holds P_ij = ½(c_ij + c_ji) — the same symmetrization
    as the dense path, restricted to the kNN union support.  Entries are
    lexsorted by (src, dst); duplicate slots carry val 0.

    ``bounds[i]:bounds[i+1]`` is row i's slice of the edge list.  The
    sorted layout is what makes the per-iteration reduction scatter-free:
    XLA's CPU scatter visits updates one by one (a segment_sum over the
    edges costs seconds at N·k ~ 10⁷), whereas cumsum + boundary-gather
    is a vectorized O(E) pass (~100 ms) — ``sparse_grad`` reduces through
    the shared :func:`repro.core.coo.segment_reduce` (the same core the
    scatter-free UMAP epoch loop uses).
    """
    src: jnp.ndarray     # (E,) int32, E = 2·N·k, sorted
    dst: jnp.ndarray     # (E,) int32
    val: jnp.ndarray     # (E,) float32, Σ val = 1
    bounds: jnp.ndarray  # (N+1,) int32: row i owns edges [bounds[i], bounds[i+1])


def calibrate_stats_knn(knn_dist: jnp.ndarray, perplexity: float,
                        weights: Optional[jnp.ndarray] = None,
                        search_iters: int = 50) -> PointStats:
    """Perplexity calibration against the kNN distances only — O(N·k).

    Same bisection as :func:`calibrate_stats`, but each row's entropy is
    computed over its k nearest neighbours instead of all N−1 points
    (the Barnes-Hut/FIt-SNE input approximation: the tail mass beyond the
    kNN radius is negligible at the calibrated sigma when k ≈ 3·perp).
    ``shift``/``zp`` normalize pc(j|i) over the kNN row.
    """
    n = knn_dist.shape[0]
    neg_d = -(knn_dist.astype(jnp.float32) ** 2)            # (N, k)
    beta = _beta_search(neg_d, jnp.log(perplexity), search_iters)
    logits = neg_d * beta[:, None]
    shift = jnp.max(logits, axis=1)
    zp = jnp.sum(jnp.exp(logits - shift[:, None]), axis=1)
    if weights is not None:
        w = weights / jnp.sum(weights)
    else:
        w = jnp.full((n,), 1.0 / n)
    return PointStats(beta=beta, shift=shift, zp=zp, w=w)


def sparse_p_from_knn(knn_idx: jnp.ndarray, knn_dist: jnp.ndarray,
                      perplexity: float,
                      weights: Optional[jnp.ndarray] = None,
                      search_iters: int = 50) -> SparseP:
    """Build the symmetrized weighted COO P from a kNN graph.

    Σ val = 1 exactly (pc rows are normalized and Σ w_i = 1), so no
    global renormalization pass is needed.
    """
    n, k = knn_idx.shape
    stats = calibrate_stats_knn(knn_dist, perplexity, weights=weights,
                                search_iters=search_iters)
    neg_d = -(knn_dist.astype(jnp.float32) ** 2)
    pc = jnp.exp(neg_d * stats.beta[:, None] - stats.shift[:, None]) \
        / stats.zp[:, None]                                  # (N, k)
    c = (stats.w[:, None] * pc).reshape(-1)                  # Σ c = 1
    rows = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    cols = knn_idx.reshape(-1).astype(jnp.int32)
    src = jnp.concatenate([rows, cols])
    dst = jnp.concatenate([cols, rows])
    val = jnp.concatenate([0.5 * c, 0.5 * c])
    src, dst, val = coo.dedupe_edges(src, dst, val)
    return SparseP(src=src, dst=dst, val=val,
                   bounds=coo.row_bounds(src, n))


def build_sparse_p(x: jnp.ndarray, perplexity: float,
                   k: Optional[int] = None,
                   weights: Optional[jnp.ndarray] = None,
                   search_iters: int = 50, block: int = 512,
                   mesh=None, method: str = "exact", ann=None) -> SparseP:
    """kNN graph + kNN calibration + symmetrized COO P — the sparse
    backend's one-time setup.  ``method``/``ann`` pick the kNN build
    (exact O(N²·D) blocked, or the sub-quadratic approximate engine —
    see ``neighbors.knn_graph``); with ``mesh`` either build row-block
    shards under ``shard_map``."""
    from repro.core import neighbors
    n = x.shape[0]
    if k is None:
        k = max(8, int(round(3.0 * perplexity)))
    k = min(k, n - 1)          # a kNN row can never exceed the other points
    idx, dist = neighbors.knn_graph(x, k, block=block, mesh=mesh,
                                    method=method, ann=ann)
    return sparse_p_from_knn(idx, dist, perplexity, weights=weights,
                             search_iters=search_iters)


def _cic_weights(y: jnp.ndarray, grid_size: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Cloud-in-cell cell indices + fractional offsets for a 2D embedding.

    The grid covers the bounding box with one spare cell of margin on
    every side and a single isotropic spacing h (the convolution kernel is
    radial, so cells must be square).  Returns (i0 (N,2) int32,
    f (N,2) fractional offsets, h scalar).
    """
    g = grid_size
    lo = jnp.min(y, axis=0)
    span = jnp.maximum(jnp.max(jnp.max(y, axis=0) - lo), 1e-9)
    h = span / (g - 3)
    u = (y - lo[None, :]) / h + 1.0                          # ∈ [1, g−2]
    i0 = jnp.clip(jnp.floor(u).astype(jnp.int32), 0, g - 2)
    f = u - i0
    return i0, f, h


def _corner_weights(f: jnp.ndarray) -> jnp.ndarray:
    """Bilinear corner weights (4, N) from fractional offsets (N, 2)."""
    fx, fy = f[:, 0], f[:, 1]
    return jnp.stack([(1 - fx) * (1 - fy), (1 - fx) * fy,
                      fx * (1 - fy), fx * fy])               # (4, N)


_CORNERS = ((0, 0), (0, 1), (1, 0), (1, 1))


def _splat_xla(i0: jnp.ndarray, f: jnp.ndarray, vals: jnp.ndarray,
               grid_size: int) -> jnp.ndarray:
    """XLA reference cloud-in-cell splat: (C, N) channel masses onto a
    (C, G, G) grid via four corner scatter-adds."""
    w = _corner_weights(f)
    grid = jnp.zeros((vals.shape[0], grid_size, grid_size), jnp.float32)
    for ci, (dx, dy) in enumerate(_CORNERS):
        grid = grid.at[:, i0[:, 0] + dx, i0[:, 1] + dy].add(
            vals * w[ci][None, :])
    return grid


def _gather_xla(field: jnp.ndarray, i0: jnp.ndarray, f: jnp.ndarray
                ) -> jnp.ndarray:
    """XLA reference cloud-in-cell gather: bilinear read of ``field``
    ((..., G, G)) at every point — returns (..., N)."""
    w = _corner_weights(f)
    acc = 0.0
    for ci, (dx, dy) in enumerate(_CORNERS):
        acc += field[..., i0[:, 0] + dx, i0[:, 1] + dy] * w[ci]
    return acc


def _grid_convolve(grid: jnp.ndarray, g: int, h: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Convolve the splatted (3, G, G) masses with the two radial tSNE
    kernels on a circulant-embedded 2G×2G domain (linear convolution).
    Returns (conv1 (3, G, G) — φ₁ * (m, m·y), conv0 (G, G) — φ₀ * m)."""
    idx = jnp.arange(2 * g)
    off = jnp.where(idx <= g, idx, idx - 2 * g).astype(jnp.float32) * h
    r2 = off[:, None] ** 2 + off[None, :] ** 2
    k0 = 1.0 / (1.0 + r2)                                    # (1+r²)⁻¹ → Z
    k1 = k0 * k0                                             # (1+r²)⁻² → force

    pad = jnp.zeros((3, 2 * g, 2 * g), jnp.float32).at[:, :g, :g].set(grid)
    mf = jnp.fft.rfft2(pad)
    conv1 = jnp.fft.irfft2(mf * jnp.fft.rfft2(k1)[None],
                           s=(2 * g, 2 * g))[:, :g, :g]      # φ₁ * (m, my)
    conv0 = jnp.fft.irfft2(mf[0] * jnp.fft.rfft2(k0),
                           s=(2 * g, 2 * g))[:g, :g]         # φ₀ * m
    return conv1, conv0


def _cfg_kernel_mode(cfg: "TsneConfig") -> Optional[str]:
    """TsneConfig.kernel_mode -> the ``mode`` argument threaded to the
    kernel call sites (None = defer to legacy interpret flag / registry)."""
    return None if cfg.kernel_mode == "auto" else cfg.kernel_mode


def fft_repulsion(y: jnp.ndarray, grid_size: int = 128, *,
                  cic: str = "xla", interpret: Optional[bool] = None,
                  mode: Optional[str] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """All-pairs repulsive field + Z by one particle-mesh FFT pass.

    Returns (rep (N, 2), z) with
        rep_i = Σ_j (1+|y_i−y_j|²)⁻² (y_i − y_j),
        z     = Σ_{i≠j} (1+|y_i−y_j|²)⁻¹.
    Splat the masses (1, y_x, y_y) onto a G×G grid (cloud-in-cell),
    convolve with the radial kernels on a zero-padded 2G×2G domain
    (circulant embedding → linear convolution), gather bilinearly.  The
    j = i term cancels in rep (zero displacement) and is subtracted from
    z in closed form (φ₀(0)·N).

    ``cic`` selects the splat/gather implementation: ``"xla"`` (scatter
    splat + gather loop) or ``"pallas"`` (the one-hot matmul tile in
    ``repro.kernels.cic``, dispatched through ``kernels.registry`` —
    ``mode`` forces a registry mode, legacy ``interpret`` maps to
    interpret/compiled, both-None auto-resolves per backend).  The FFT
    convolution is XLA-native either way.
    """
    if cic not in CIC_PATHS:
        raise ValueError(f"unknown cic {cic!r}; want one of {CIC_PATHS}")
    n = y.shape[0]
    g = grid_size
    y = y.astype(jnp.float32)
    i0, f, h = _cic_weights(y, g)

    if cic == "pallas":
        from repro.kernels import ops
        masses = jnp.stack([jnp.ones((n,), jnp.float32),
                            y[:, 0], y[:, 1]], axis=1)       # (N, 3)
        grid = ops.cic_splat(i0, f, masses, g, interpret=interpret,
                             mode=mode)
    else:
        vals = jnp.stack([jnp.ones((n,), jnp.float32), y[:, 0], y[:, 1]])
        grid = _splat_xla(i0, f, vals, g)

    conv1, conv0 = _grid_convolve(grid, g, h)

    if cic == "pallas":
        from repro.kernels import ops
        fields = jnp.concatenate([conv1, conv0[None]], axis=0)
        got = ops.cic_gather(fields, i0, f, interpret=interpret,
                             mode=mode)                      # (N, 4)
        s1, sy, phi0 = got[:, 0], got[:, 1:3], got[:, 3]
        z = jnp.maximum(jnp.sum(phi0) - n, 1e-12)
        return s1[:, None] * y - sy, z

    s1 = _gather_xla(conv1[0], i0, f)                        # Σ_j φ₁
    sy = _gather_xla(conv1[1:], i0, f)                       # (2, N) Σ_j φ₁·y_j
    z = jnp.maximum(jnp.sum(_gather_xla(conv0, i0, f)) - n,
                    1e-12)                                   # drop self terms
    rep = s1[:, None] * y - sy.T
    return rep, z


def _edge_attraction(y: jnp.ndarray, src: jnp.ndarray, dst: jnp.ndarray,
                     pe: jnp.ndarray, bounds: jnp.ndarray,
                     mode: Optional[str]
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Attraction Σ_j pe_ij·num_ij·(y_i − y_j) per src row, and num per
    edge, for a src-sorted edge list with row ``bounds``.

    The reduction is scatter-free: Σ over row i is a cumsum difference
    at the precomputed row bounds — one vectorized O(E) pass (XLA CPU
    scatter walks updates serially, ~100× slower at E ~ 10⁷), shared
    with the UMAP epoch loop.  Per-edge values stay 1-D, one array per
    embedding coordinate: a TPU tiles the last dim of an array to 128
    lanes, so an (E, 2) intermediate takes 64x its size on the chip
    (at CANCER_1M's E = 1.8e8 one such array alone would be 92 GB).  The
    arithmetic is element for element the (E, 2) form's.
    """
    diff = [y[:, c][src] - y[:, c][dst] for c in range(y.shape[1])]
    num = 1.0 / (1.0 + sum(d * d for d in diff))             # (E,)
    w = pe * num
    att = jnp.stack([coo.segment_reduce(w * d, bounds, mode=mode)
                     for d in diff], axis=1)
    return att, num


def sparse_grad(y: jnp.ndarray, sp: SparseP, exaggeration=1.0,
                grid_size: int = 128, *, cic: str = "xla",
                interpret: Optional[bool] = None,
                mode: Optional[str] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One sparse-backend gradient evaluation: O(N·k + G²·log G).

    Returns (grad (N, 2), KL of the exaggerated sparse P against Q) —
    the same decomposition the exact backends compute, with the P-sum
    restricted to the kNN support and the Q-sum on the FFT grid
    (``cic``/``interpret`` select its splat/gather path).
    """
    exaggeration = jnp.asarray(exaggeration, jnp.float32)
    pe = exaggeration * sp.val
    att, num = _edge_attraction(y, sp.src, sp.dst, pe, sp.bounds, mode)
    rep, z = fft_repulsion(y, grid_size, cic=cic, interpret=interpret,
                           mode=mode)
    grad = 4.0 * (att - rep / z)
    # KL partials over the sparse support (pe = 0 elsewhere):
    #   KL = Σ pe log pe − Σ pe log num + (Σ pe)·log Z,  Σ pe = exag
    a = jnp.sum(jnp.where(pe > 0,
                          pe * jnp.log(jnp.maximum(pe, 1e-37)), 0.0))
    b = jnp.sum(pe * jnp.log(jnp.maximum(num, 1e-37)))
    kl = a - b + exaggeration * jnp.log(z)
    return grad, kl


# ------------------------------------------------------------- mesh sharding
# Row-block-sharded sparse backend: the whole iteration runs inside
# shard_map on a 1-D embed mesh (core.mesh).  Device s owns the contiguous
# row block [s·rows_per, (s+1)·rows_per) of the optimizer state AND the
# matching contiguous slice of the src-sorted COO edge list
# (coo.ShardedEdgeLayout), so the attraction reduction is the same local
# cumsum-difference segment_reduce the single-device path runs — P_ij only
# ever deposits into src rows (the symmetrized COO carries both
# directions), so tSNE needs NO dst-side exchange at all.  The repulsion
# grid is a sum of per-point splats: each device splats its own rows and
# ONE psum of the (3, G, G) grid masses replicates the total; the FFT then
# runs replicated on the tiny G×G grid and each device gathers its own
# rows back.  Collective contract per iteration (jaxpr-pinned in
# tests/test_mesh_embed.py): one all_gather (the row-block positions) +
# psums of fixed-size partials (grid, Z, KL terms, the centering mean) —
# no cross-device scatter anywhere, and the only scatter primitives of any
# kind are the same four per-device CIC corner splats the single-device
# backend runs.

class ShardedSparseP(NamedTuple):
    """``SparseP`` re-laid-out for a 1-D embed mesh: per-block contiguous
    edge slices (``coo.ShardedEdgeLayout``) + the matching (S, Ep) values
    (zeroed on padded slots).  Built host-side once at setup."""
    layout: coo.ShardedEdgeLayout
    val: jnp.ndarray         # (S, Ep) float32, padded slots carry 0


def shard_sparse_p(sp: SparseP, n: int, n_shards: int) -> ShardedSparseP:
    """Split a (src-sorted) ``SparseP`` into per-row-block edge slices —
    host-side, setup-time (per-block counts are data-dependent)."""
    layout = coo.shard_edge_layout(np.asarray(sp.src), np.asarray(sp.dst),
                                   n, n_shards)
    return ShardedSparseP(layout=layout,
                          val=coo.shard_payload(layout, sp.val))


def _fft_repulsion_shard(y_blk: jnp.ndarray, live_blk: jnp.ndarray,
                         y_full: jnp.ndarray, live_full: jnp.ndarray,
                         grid_size: int, axis: str, n: int, *,
                         cic: str = "xla", interpret: Optional[bool] = None,
                         mode: Optional[str] = None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-device body of :func:`fft_repulsion` on a row-block mesh.

    Grid geometry comes from the replicated ``y_full`` (live rows only, so
    padded tail rows never stretch the bounding box); each device splats
    its own block's masses, the grids ``psum``-merge, the FFT convolution
    runs replicated, and the local rows gather back.  Returns
    (rep (rows_per, 2), z) — ``z`` is replicated."""
    g = grid_size
    y_blk = y_blk.astype(jnp.float32)
    lo = jnp.min(jnp.where(live_full[:, None], y_full, jnp.inf), axis=0)
    hi = jnp.max(jnp.where(live_full[:, None], y_full, -jnp.inf), axis=0)
    span = jnp.maximum(jnp.max(hi - lo), 1e-9)
    h = span / (g - 3)
    u = (y_blk - lo[None, :]) / h + 1.0
    i0 = jnp.clip(jnp.floor(u).astype(jnp.int32), 0, g - 2)
    f = u - i0
    mass = live_blk.astype(jnp.float32)

    if cic == "pallas":
        from repro.kernels import ops
        masses = jnp.stack([mass, y_blk[:, 0] * mass,
                            y_blk[:, 1] * mass], axis=1)     # (B, 3)
        grid = ops.cic_splat(i0, f, masses, g, interpret=interpret,
                             mode=mode)
    else:
        vals = jnp.stack([mass, y_blk[:, 0] * mass, y_blk[:, 1] * mass])
        grid = _splat_xla(i0, f, vals, g)
    grid = jax.lax.psum(grid, axis)                          # THE exchange

    conv1, conv0 = _grid_convolve(grid, g, h)

    if cic == "pallas":
        from repro.kernels import ops
        fields = jnp.concatenate([conv1, conv0[None]], axis=0)
        got = ops.cic_gather(fields, i0, f, interpret=interpret,
                             mode=mode)
        s1, sy, phi0 = got[:, 0], got[:, 1:3].T, got[:, 3]
    else:
        s1 = _gather_xla(conv1[0], i0, f)
        sy = _gather_xla(conv1[1:], i0, f)                   # (2, B)
        phi0 = _gather_xla(conv0, i0, f)
    z = jnp.maximum(jax.lax.psum(jnp.sum(phi0 * mass), axis) - n, 1e-12)
    rep = s1[:, None] * y_blk - sy.T
    return rep, z


def sparse_grad_shard(y_blk: jnp.ndarray, layout: coo.ShardedEdgeLayout,
                      val: jnp.ndarray, y_full: jnp.ndarray,
                      exaggeration, grid_size: int, axis: str, n: int, *,
                      cic: str = "xla", interpret: Optional[bool] = None,
                      mode: Optional[str] = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-device sparse gradient: the shard_map body mirroring
    :func:`sparse_grad`.  ``layout``/``val`` are ONE device's squeezed
    (Ep,)-slices; returns (grad (rows_per, 2), KL) with KL replicated."""
    exaggeration = jnp.asarray(exaggeration, jnp.float32)
    rows_per = layout.src_bounds.shape[0] - 1
    n_pad = layout.dst_bounds.shape[0] - 1
    pe = exaggeration * val                                  # 0 on padding
    # local rows own their full edge slice (blocks split at row
    # boundaries), so the attraction reduction is entirely local
    att, num = _edge_attraction(y_full, layout.src, layout.dst, pe,
                                layout.src_bounds, mode)
    live_blk = layout.row_offset + jnp.arange(rows_per) < n
    live_full = jnp.arange(n_pad) < n
    rep, z = _fft_repulsion_shard(y_blk, live_blk, y_full, live_full,
                                  grid_size, axis, n, cic=cic,
                                  interpret=interpret, mode=mode)
    grad = 4.0 * (att - rep / z)
    grad = jnp.where(live_blk[:, None], grad, 0.0)
    a = jax.lax.psum(jnp.sum(jnp.where(
        pe > 0, pe * jnp.log(jnp.maximum(pe, 1e-37)), 0.0)), axis)
    b = jax.lax.psum(jnp.sum(pe * jnp.log(jnp.maximum(num, 1e-37))), axis)
    kl = a - b + exaggeration * jnp.log(z)
    return grad, kl


def _momentum_update_shard(state: TsneState, grad: jnp.ndarray, mom,
                           cfg: TsneConfig, axis: str, live_blk: jnp.ndarray,
                           n: int) -> TsneState:
    """Row-block momentum update: identical math to
    :func:`_momentum_update` except the recentering mean is a ``psum`` of
    per-block partial sums over the live rows."""
    same_sign = jnp.sign(grad) == jnp.sign(state.velocity)
    gains = jnp.where(same_sign, state.gains * 0.8, state.gains + 0.2)
    gains = jnp.maximum(gains, cfg.min_gain)
    vel = mom * state.velocity - cfg.learning_rate * gains * grad
    y = state.y + vel
    total = jax.lax.psum(
        jnp.sum(jnp.where(live_blk[:, None], y, 0.0), axis=0), axis)
    y = y - (total / n)[None, :]
    return TsneState(y, vel, gains)


@functools.partial(jax.jit, static_argnames=("cfg", "count", "grid_size",
                                             "mesh", "n"))
def _sparse_stage_mesh(state: TsneState, kls: jnp.ndarray,
                       ssp: ShardedSparseP, it0: jnp.ndarray, *,
                       cfg: TsneConfig, count: int, grid_size: int,
                       mesh, n: int) -> Tuple[TsneState, jnp.ndarray]:
    """``count`` mesh-parallel optimizer iterations at a fixed grid size —
    the sharded twin of :func:`_sparse_stage`.  State rows and edge slices
    stay on their devices across the whole ``fori_loop``; per iteration the
    only collectives are one all_gather of the block positions and the
    fixed-size psums (grid, Z, KL, centering)."""
    axis = mesh_mod.mesh_axis(mesh)
    P = mesh_mod.P
    lay_specs = jax.tree_util.tree_map(lambda _: P(axis), ssp)
    state_specs = TsneState(P(axis), P(axis), P(axis))

    @mesh_mod.shard_map_compat(
        mesh=mesh, in_specs=(state_specs, P(), lay_specs, P()),
        out_specs=(state_specs, P()))
    def spmd(state, kls, ssp, it0):
        # (S, ...) leaves arrive as (1, ...) per device — drop the axis
        lay = jax.tree_util.tree_map(lambda a: a[0], ssp.layout)
        val = ssp.val[0]
        rows_per = lay.src_bounds.shape[0] - 1
        live_blk = lay.row_offset + jnp.arange(rows_per) < n

        def step(i, carry):
            st, kls = carry
            it = it0 + i
            exag, mom = _phase(it, cfg)
            y_full = jax.lax.all_gather(st.y, axis, axis=0, tiled=True)
            grad, kl = sparse_grad_shard(
                st.y, lay, val, y_full, exag, grid_size, axis, n,
                cic=cfg.cic, mode=_cfg_kernel_mode(cfg))
            st = _momentum_update_shard(st, grad, mom, cfg, axis,
                                        live_blk, n)
            return st, kls.at[it].set(kl)

        return jax.lax.fori_loop(0, count, step, (state, kls))

    return spmd(state, kls, ssp, it0)


def _run_tsne_sparse_mesh(key: jax.Array, x: jnp.ndarray, weights, init, *,
                          cfg: TsneConfig, mesh
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mesh-parallel sparse optimizer (fixed or span-adaptive G).

    Setup: sharded kNN build + COO P (jitted), then the host slices the
    src-sorted edge list into per-row-block shards (shapes are
    data-dependent, so this is a one-time concrete pass).  The optimizer
    then runs in jitted mesh stages; with ``grid_interval > 0`` the host
    checks the span between stages and doubles G exactly like the
    single-device staged driver."""
    n = x.shape[0]
    n_shards = mesh_mod.axis_size(mesh, mesh_mod.mesh_axis(mesh))
    rows_per, n_pad = mesh_mod.row_block(n, n_shards)

    sp = _sparse_setup_p_mesh(x, weights, cfg=cfg, mesh=mesh)
    ssp = shard_sparse_p(sp, n, n_shards)

    # identical draws to the single-device path, then padded tail rows
    y0 = init if init is not None else \
        1e-4 * jax.random.normal(key, (n, cfg.dims))
    y0 = jnp.pad(y0, [(0, n_pad - n), (0, 0)])
    state = TsneState(y=y0, velocity=jnp.zeros_like(y0),
                      gains=jnp.ones_like(y0))
    kls = jnp.zeros((cfg.n_iter,))
    g = cfg.grid_size
    it = 0
    while it < cfg.n_iter:
        count = cfg.n_iter - it if cfg.grid_interval <= 0 else \
            min(cfg.adaptive_interval, cfg.n_iter - it)
        state, kls = _sparse_stage_mesh(
            state, kls, ssp, jnp.asarray(it, jnp.int32), cfg=cfg,
            count=count, grid_size=g, mesh=mesh, n=n)
        it += count
        if cfg.grid_interval > 0 and it < cfg.n_iter:
            y_live = state.y[:n]
            span = float(jnp.max(jnp.max(y_live, axis=0)
                                 - jnp.min(y_live, axis=0)))
            g = _grid_for_span(span, g, cfg)
    return state.y[:n], kls


@functools.partial(jax.jit, static_argnames=("cfg", "mesh"))
def _sparse_setup_p_mesh(x: jnp.ndarray, weights, *, cfg: TsneConfig,
                         mesh) -> SparseP:
    """Jitted sparse-P setup with the kNN build sharded over the mesh."""
    return build_sparse_p(x, cfg.perplexity, k=cfg.knn or None,
                          weights=weights,
                          search_iters=cfg.sigma_search_iters,
                          block=cfg.block, mesh=mesh,
                          method=cfg.knn_method, ann=cfg.ann)


def kl_divergence(p: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    n = y.shape[0]
    num = 1.0 / (1.0 + pairwise_sq_dists(y))
    num = num.at[jnp.arange(n), jnp.arange(n)].set(0.0)
    q = jnp.maximum(num / jnp.sum(num), 1e-12)
    return jnp.sum(p * (jnp.log(p) - jnp.log(q)))


def _grad_and_kl(p: jnp.ndarray, y: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact tSNE gradient (matmul form) + current KL — dense backend."""
    n = y.shape[0]
    num = 1.0 / (1.0 + pairwise_sq_dists(y))                 # (N, N)
    num = num.at[jnp.arange(n), jnp.arange(n)].set(0.0)
    z = jnp.sum(num)
    q = jnp.maximum(num / z, 1e-12)
    pq = (p - q) * num                                       # (N, N)
    # grad_i = 4 [ (sum_j pq_ij) y_i - sum_j pq_ij y_j ]
    grad = 4.0 * (jnp.sum(pq, axis=1, keepdims=True) * y - pq @ y)
    kl = jnp.sum(p * (jnp.log(p) - jnp.log(q)))
    return grad, kl


def _tiled_grad_kl(x: jnp.ndarray, y: jnp.ndarray, stats: PointStats,
                   exaggeration: jnp.ndarray, n_valid: int, block: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Block-streamed gradient + KL: peak memory O(block · N).

    All inputs padded to a multiple of ``block`` (padded rows carry w = 0
    and are masked out of every pair).  Two passes, like the Pallas
    kernel: Z is a global reduction that must precede the force weighting.
    """
    npad, dims = y.shape
    nb = npad // block
    ids = jnp.arange(npad)
    col_live = ids[None, :] < n_valid

    def pair_mask(idc):
        return (idc[:, None] != ids[None, :]) & \
            (idc[:, None] < n_valid) & col_live

    def z_chunk(args):
        yc, idc = args
        num = 1.0 / (1.0 + pairwise_sq_dists(yc, y))
        return jnp.sum(jnp.where(pair_mask(idc), num, 0.0))

    chunks_y = y.reshape(nb, block, dims)
    chunks_id = ids.reshape(nb, block)
    z = jnp.sum(jax.lax.map(z_chunk, (chunks_y, chunks_id)))

    beta, shift, zp, w = stats

    def force_chunk(args):
        xc, yc, bc, mc, zc, wc, idc = args
        mask = pair_mask(idc)
        d2x = pairwise_sq_dists(xc, x)
        pc_ij = jnp.exp(-bc[:, None] * d2x - mc[:, None]) / zc[:, None]
        pc_ji = jnp.exp(-beta[None, :] * d2x - shift[None, :]) / zp[None, :]
        p = jnp.where(mask, 0.5 * (wc[:, None] * pc_ij + w[None, :] * pc_ji),
                      0.0)
        num = 1.0 / (1.0 + pairwise_sq_dists(yc, y))
        num = jnp.where(mask, num, 0.0)
        q = num / z
        pe = exaggeration * p
        pq = (pe - q) * num
        f = 4.0 * (jnp.sum(pq, axis=1, keepdims=True) * yc - pq @ y)
        # KL partials: Σ pe log pe and Σ pe log num (q = num/Z folds in later)
        a = jnp.sum(jnp.where(pe > 0, pe * jnp.log(jnp.maximum(pe, 1e-37)),
                              0.0))
        b = jnp.sum(jnp.where(pe > 0, pe * jnp.log(jnp.maximum(num, 1e-37)),
                              0.0))
        return f, a, b

    xs = (x.reshape(nb, block, -1), chunks_y,
          beta.reshape(nb, block), shift.reshape(nb, block),
          zp.reshape(nb, block), w.reshape(nb, block), chunks_id)
    f, a, b = jax.lax.map(force_chunk, xs)
    kl = jnp.sum(a) - jnp.sum(b) + exaggeration * jnp.log(z)
    return f.reshape(npad, dims), kl


def embedding_grad(x: jnp.ndarray, y: jnp.ndarray, stats: PointStats,
                   exaggeration=1.0, *, backend: str = "tiled",
                   block: int = 512, interpret: Optional[bool] = None,
                   mode: Optional[str] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One tSNE gradient evaluation on any backend — test/bench surface.

    Returns (grad (N, dims), KL of the exaggerated P against current Q).
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; want one of {BACKENDS}")
    if backend == "sparse":
        raise ValueError(
            "the sparse backend is calibrated from the kNN graph, not "
            "PointStats — use build_sparse_p(...) once, then sparse_grad()")
    exaggeration = jnp.asarray(exaggeration, jnp.float32)
    if backend == "dense":
        return _grad_and_kl(p_from_stats(x, stats) * exaggeration, y)
    if backend == "pallas":
        from repro.kernels import ops
        return ops.tsne_step_fused(
            x, y, stats.beta, stats.zp, shift=stats.shift, weights=stats.w,
            exaggeration=exaggeration, block=min(block, x.shape[0]),
            interpret=interpret, mode=mode, return_kl=True)
    n = x.shape[0]
    block = min(block, n)
    pad = functools.partial(_pad_rows, block=block)
    spad = PointStats(beta=pad(stats.beta), shift=pad(stats.shift),
                      zp=pad(stats.zp, value=1), w=pad(stats.w))
    f, kl = _tiled_grad_kl(pad(x), pad(y), spad, exaggeration,
                           n_valid=n, block=block)
    return f[:n], kl


class TsneState(NamedTuple):
    y: jnp.ndarray
    velocity: jnp.ndarray
    gains: jnp.ndarray


def _momentum_update(state: TsneState, grad: jnp.ndarray, mom, cfg: TsneConfig
                     ) -> TsneState:
    """One momentum + per-parameter-gains optimizer update (recentered)."""
    same_sign = jnp.sign(grad) == jnp.sign(state.velocity)
    gains = jnp.where(same_sign, state.gains * 0.8, state.gains + 0.2)
    gains = jnp.maximum(gains, cfg.min_gain)
    vel = mom * state.velocity - cfg.learning_rate * gains * grad
    y = state.y + vel
    y = y - jnp.mean(y, axis=0, keepdims=True)
    return TsneState(y, vel, gains)


def _phase(i, cfg: TsneConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Staged-schedule scalars (exaggeration, momentum) at iteration i."""
    exag = jnp.where(i < cfg.exaggeration_iters,
                     cfg.early_exaggeration, 1.0)
    mom = jnp.where(i < cfg.momentum_switch,
                    cfg.momentum_start, cfg.momentum_final)
    return exag, mom


@functools.partial(jax.jit, static_argnames=("cfg", "backend"))
def _run_tsne(key: jax.Array, x: jnp.ndarray, weights, init, *,
              cfg: TsneConfig, backend: str
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    n = x.shape[0]
    if backend == "sparse":
        sp = build_sparse_p(x, cfg.perplexity, k=cfg.knn or None,
                            weights=weights,
                            search_iters=cfg.sigma_search_iters,
                            block=cfg.block,
                            method=cfg.knn_method, ann=cfg.ann)

        def grad_fn(y, exag):
            return sparse_grad(y, sp, exag, grid_size=cfg.grid_size,
                               cic=cfg.cic, mode=_cfg_kernel_mode(cfg))
    else:
        stats = calibrate_stats(x, cfg.perplexity, weights=weights,
                                search_iters=cfg.sigma_search_iters,
                                block=cfg.block)
        if backend == "dense":
            p = p_from_stats(x, stats)

            def grad_fn(y, exag):
                return _grad_and_kl(p * exag, y)
        else:
            def grad_fn(y, exag):
                return embedding_grad(x, y, stats, exag, backend=backend,
                                      block=cfg.block,
                                      mode=_cfg_kernel_mode(cfg))

    y0 = init if init is not None else \
        1e-4 * jax.random.normal(key, (n, cfg.dims))
    state = TsneState(y=y0, velocity=jnp.zeros_like(y0),
                      gains=jnp.ones_like(y0))

    def step(i, carry):
        state, kls = carry
        exag, mom = _phase(i, cfg)
        grad, kl = grad_fn(state.y, exag)
        return _momentum_update(state, grad, mom, cfg), kls.at[i].set(kl)

    state, kls = jax.lax.fori_loop(
        0, cfg.n_iter, step, (state, jnp.zeros((cfg.n_iter,))))
    return state.y, kls


# ------------------------------------------------------------------ adaptive G
# FIt-SNE grows the interpolation grid with the embedding span instead of
# re-spacing a fixed G×G grid: the cell spacing h stays (approximately)
# constant, so the repulsion field's resolution does not degrade as early
# exaggeration relaxes and the embedding expands 10-100×.  Shapes must be
# static under jit, so the optimizer runs in STAGES of
# ``cfg.adaptive_interval`` iterations: each stage is one jitted call with
# a static G, and between stages the host checks the span and doubles G
# when span/(G−3) outgrows ``cfg.grid_interval`` (monotone, capped at
# ``cfg.grid_max``).  G only ever takes values grid_size·2^m, so the whole
# run retraces at most log₂(grid_max/grid_size) times.

@functools.partial(jax.jit, static_argnames=("cfg",))
def _sparse_setup(key: jax.Array, x: jnp.ndarray, weights, init, *,
                  cfg: TsneConfig) -> Tuple[SparseP, TsneState]:
    """One-time sparse-backend setup: COO P + optimizer init."""
    sp = build_sparse_p(x, cfg.perplexity, k=cfg.knn or None,
                        weights=weights,
                        search_iters=cfg.sigma_search_iters,
                        block=cfg.block,
                        method=cfg.knn_method, ann=cfg.ann)
    y0 = init if init is not None else \
        1e-4 * jax.random.normal(key, (x.shape[0], cfg.dims))
    return sp, TsneState(y=y0, velocity=jnp.zeros_like(y0),
                         gains=jnp.ones_like(y0))


@functools.partial(jax.jit, static_argnames=("cfg", "count", "grid_size"))
def _sparse_stage(state: TsneState, kls: jnp.ndarray, sp: SparseP,
                  it0: jnp.ndarray, *, cfg: TsneConfig, count: int,
                  grid_size: int) -> Tuple[TsneState, jnp.ndarray]:
    """``count`` optimizer iterations at a fixed grid size.

    ``it0`` (the global iteration offset) is traced, so the stage function
    retraces only when (count, grid_size) changes — the schedule scalars
    still switch at the right global iteration.
    """
    def step(i, carry):
        state, kls = carry
        it = it0 + i
        exag, mom = _phase(it, cfg)
        grad, kl = sparse_grad(state.y, sp, exag, grid_size=grid_size,
                               cic=cfg.cic, mode=_cfg_kernel_mode(cfg))
        return _momentum_update(state, grad, mom, cfg), kls.at[it].set(kl)

    return jax.lax.fori_loop(0, count, step, (state, kls))


def _grid_for_span(span: float, g: int, cfg: TsneConfig) -> int:
    """Smallest doubling of the current G that keeps the cell spacing
    h = span/(G−3) at or under the target ``cfg.grid_interval``."""
    while g < cfg.grid_max and span / (g - 3) > cfg.grid_interval:
        g *= 2
    return g


def _run_tsne_sparse_adaptive(key: jax.Array, x: jnp.ndarray, weights, init,
                              *, cfg: TsneConfig
                              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Staged sparse optimizer with span-adaptive repulsion grid."""
    sp, state = _sparse_setup(key, x, weights, init, cfg=cfg)
    kls = jnp.zeros((cfg.n_iter,))
    g = cfg.grid_size
    it = 0
    while it < cfg.n_iter:
        count = min(cfg.adaptive_interval, cfg.n_iter - it)
        state, kls = _sparse_stage(
            state, kls, sp, jnp.asarray(it, jnp.int32), cfg=cfg,
            count=count, grid_size=g)
        it += count
        span = float(jnp.max(jnp.max(state.y, axis=0)
                             - jnp.min(state.y, axis=0)))
        g = _grid_for_span(span, g, cfg)
    return state.y, kls


def run_tsne(key: jax.Array, x: jnp.ndarray, cfg: TsneConfig,
             weights: Optional[jnp.ndarray] = None,
             backend: Optional[str] = None,
             mesh=None, init: Optional[jnp.ndarray] = None
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full tSNE: returns (embedding (N, dims), KL trace (n_iter,)).

    ``backend`` overrides ``cfg.backend``; Pallas call sites resolve
    their tier through ``kernels.registry`` (``cfg.kernel_mode``; auto is
    compiled on an accelerator, interpret on the CPU).  ``mesh`` (``None``
    | device count | 1-D ``Mesh``, see ``core.mesh``) runs the whole
    sparse optimizer row-block-sharded under ``shard_map`` — sparse
    backend only (the dense/tiled/pallas backends are O(N²) and stay
    single-device).

    ``init`` seeds the optimizer at the given (N, dims) float coordinates
    instead of the 1e-4·normal cold start — the warm-start hook the
    online service uses to resume from a previous embedding (callers
    normally pair it with ``exaggeration_iters=0``: early exaggeration
    would blow a converged init apart).  Works on every backend and on
    the mesh path; validated for shape/dtype up front.
    """
    backend = backend or cfg.backend
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; want one of {BACKENDS}")
    if backend == "sparse" and cfg.dims != 2:
        raise ValueError(
            f"sparse backend splats onto a 2D grid; got dims={cfg.dims}")
    if cfg.cic not in CIC_PATHS:
        raise ValueError(f"unknown cic {cfg.cic!r}; want one of {CIC_PATHS}")
    if cfg.kernel_mode not in ("auto",) + registry_mod.MODES:
        raise ValueError(
            f"unknown kernel_mode {cfg.kernel_mode!r}; want one of "
            f"{('auto',) + registry_mod.MODES}")
    init = validate_init(init, x.shape[0], cfg.dims)
    if cfg.n_iter == 0:
        # degenerate but load-bearing for the warm-start contract: the
        # returned embedding IS iteration 0 (the init, bit-exact), and no
        # optimizer machinery may touch it (the fori_loop body would still
        # trace a scatter into the empty KL trace)
        y0 = init if init is not None else \
            1e-4 * jax.random.normal(key, (x.shape[0], cfg.dims))
        return y0, jnp.zeros((0,), jnp.float32)
    mesh = mesh_mod.resolve_mesh(mesh)
    if mesh is not None:
        if backend != "sparse":
            raise ValueError(
                f"mesh-parallel tSNE needs backend='sparse'; got {backend!r}")
        return _run_tsne_sparse_mesh(key, x, weights, init, cfg=cfg,
                                     mesh=mesh)
    if backend == "sparse" and cfg.grid_interval > 0:
        return _run_tsne_sparse_adaptive(key, x, weights, init, cfg=cfg)
    return _run_tsne(key, x, weights, init, cfg=cfg, backend=backend)
