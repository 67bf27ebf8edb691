"""Vanilla UMAP in pure JAX — the paper's second downstream embedder.

Faithful to McInnes-Healy-Melville 2018 (and the umap-learn reference):

* exact kNN graph (paper regime: N ≤ 2·10⁴ representatives, so brute-force
  pairwise distances on the MXU beat approximate NN),
* fuzzy simplicial set: per-point rho (distance to nearest neighbour) and
  sigma from binary search so Σ_j exp(−(d−rho)/sigma) = log₂(k),
* probabilistic t-conorm symmetrization  a ⊕ a' = a + a' − a∘a',
* (a, b) curve fit from (spread, min_dist) by least squares,
* SGD over the cross-entropy with negative sampling.

JAX adaptation: umap-learn's per-edge asynchronous SGD ("hogwild") is
host-sequential and shape-dynamic.  We instead run *epoch-batched* SGD:
each epoch applies the attractive gradient of every edge (weighted by the
fuzzy membership, equivalent in expectation to umap-learn's
sample-by-weight schedule) and `neg_rate` uniformly-sampled repulsive
pairs per edge — all static shapes, all fused by XLA.  This is the same
estimator, batched; convergence behaviour matches (tested on blobs).

The per-epoch reduction of E = N·k per-edge forces into per-point deltas
is *scatter-free*: at setup :func:`repro.core.coo.edge_layout` sorts the
edges by src (stable — the fuzzy-set edge list is already src-sorted, so
edge order and the per-edge RNG stream are unchanged), precomputes the
dst-sorted ordering plus the gather permutation between the two, and the
epoch body reduces each endpoint's contributions with one O(E) cumsum
differenced at the precomputed row bounds
(:func:`repro.core.coo.segment_reduce`) — the same machinery as the
sparse tSNE backend.  XLA's CPU scatter walks updates serially (~100×
slower at E ~ 10⁷), so replacing the two ``.at[].add`` scatters per epoch
is what lets ``embedder="umap"`` run at the same N = 10⁵–10⁶
representative counts as sparse tSNE
(benchmarks/bench_embed_throughput.py tracks epochs/sec against the
frozen scatter baseline; the epoch jaxpr is pinned scatter-free in
tests/test_umap_scatter_free.py).

Weighted extension (SnS): HH counts enter as per-point mass, scaling each
point's outgoing memberships — representatives of dense cells attract
proportionally more, mirroring the paper's replica weighting.

Mesh-parallel path (``run_umap(mesh=...)`` — ``None`` | device count |
1-D ``Mesh``, plumbing in :mod:`repro.core.mesh`): the SGD loop runs
inside ``shard_map`` with each device owning a contiguous row block of y
and the matching contiguous slice of the src-sorted edge list
(``coo.ShardedEdgeLayout``).  Per epoch: one ``all_gather`` of the block
positions, local src-side reduction, and ONE ``psum`` of the full-length
dst-side partials — zero scatter primitives of any kind (jaxpr-pinned in
tests/test_mesh_embed.py).  Negative samples are drawn as the full (E, R)
array from the replicated key and gathered per block, so the mesh run is
draw-for-draw aligned with the single-device stream.
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
from repro.core import neighbors
from repro.core.neighbors import knn_graph  # noqa: F401  (public re-export)


@dataclasses.dataclass(frozen=True)
class UmapConfig:
    dims: int = 2
    n_neighbors: int = 15
    min_dist: float = 0.1
    spread: float = 1.0
    n_epochs: int = 300
    learning_rate: float = 1.0
    neg_rate: int = 5
    init_scale: float = 10.0
    sigma_search_iters: int = 50
    block: int = 4096              # kNN row-block; N <= block -> dense path
    # kNN build: "exact" | "auto" | "ann" — "auto" switches to the
    # approximate engine (core.ann) above AnnConfig.auto_threshold
    # points; ``ann`` carries its knobs (an ann.AnnConfig)
    knn_method: str = "auto"
    ann: Optional[object] = None
    # kernel dispatch mode for the segment-reduce call sites (see
    # kernels.registry): "auto" keeps the cumsum path on every backend;
    # other values force one mode
    kernel_mode: str = "auto"


def _cfg_kernel_mode(cfg: UmapConfig) -> Optional[str]:
    """UmapConfig.kernel_mode -> the ``mode`` threaded to segment_reduce
    (None = defer to the registry's process-level resolution)."""
    return None if cfg.kernel_mode == "auto" else cfg.kernel_mode


@functools.lru_cache(maxsize=None)
def fit_ab(spread: float, min_dist: float) -> Tuple[float, float]:
    """Least-squares fit of 1/(1+a d^{2b}) to the target membership curve
    (host-side, same construction as umap-learn).  Cached per
    (spread, min_dist): the call happens at trace time inside the jitted
    ``optimize_embedding``, so every retrace (new static shape / cfg)
    would otherwise re-run the scipy ``curve_fit``."""
    from scipy.optimize import curve_fit
    xs = np.linspace(0, 3.0 * spread, 300)
    ys = np.where(xs < min_dist, 1.0, np.exp(-(xs - min_dist) / spread))

    def curve(x, a, b):
        return 1.0 / (1.0 + a * x ** (2 * b))

    (a, b), _ = curve_fit(curve, xs, ys, p0=(1.0, 1.0), maxfev=10_000)
    return float(a), float(b)


def fuzzy_simplicial_set(knn_idx: jnp.ndarray, knn_dist: jnp.ndarray,
                         weights: Optional[jnp.ndarray] = None,
                         search_iters: int = 50,
                         symmetrize: str = "sparse"
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Memberships on the kNN edges + symmetrized graph.

    Returns (edges (E,2) int32, membership (E,) float32) with E = N·k
    (each directed edge; symmetrization by the probabilistic t-conorm
    a ⊕ a' = a + a' − a·a').  ``symmetrize="sparse"`` (default) matches
    reverse edges by sorted-key binary search — no (N, N) temp;
    ``"dense"`` keeps the scatter-max reference path for small N."""
    n, k = knn_idx.shape
    rho = knn_dist[:, 0]
    target = jnp.log2(float(k))

    lo = jnp.full((n,), 1e-6)
    hi = jnp.full((n,), 1e6)

    def bisect(_, state):
        lo, hi = state
        mid = 0.5 * (lo + hi)
        d = jnp.maximum(knn_dist - rho[:, None], 0.0)
        s = jnp.sum(jnp.exp(-d / mid[:, None]), axis=1)
        too_big = s > target
        return jnp.where(too_big, lo, mid), jnp.where(too_big, mid, hi)

    lo, hi = jax.lax.fori_loop(0, search_iters, bisect, (lo, hi))
    sigma = 0.5 * (lo + hi)
    memb = jnp.exp(-jnp.maximum(knn_dist - rho[:, None], 0.0)
                   / sigma[:, None])                          # (N, k)
    if weights is not None:
        w = weights / jnp.mean(weights)
        memb = jnp.minimum(memb * w[:, None], 1.0)

    rows = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    cols = knn_idx.reshape(-1).astype(jnp.int32)
    vals = memb.reshape(-1)
    if symmetrize == "sparse":
        rev = neighbors.reverse_edge_values(knn_idx, memb, rows, cols,
                                            vals, n)
        edge_vals = vals + rev - vals * rev
    elif symmetrize == "dense":
        # reference path: dense lookup of reverse membership via scatter-max
        dense = jnp.zeros((n, n)).at[rows, cols].max(vals)
        sym = dense + dense.T - dense * dense.T
        edge_vals = sym[rows, cols]
    else:
        raise ValueError(f"unknown symmetrize {symmetrize!r}")
    edges = jnp.stack([rows, cols], axis=1)
    return edges, edge_vals


class _OptState(NamedTuple):
    y: jnp.ndarray
    key: jax.Array


def epoch_delta(y: jnp.ndarray, layout: coo.EdgeLayout, memb_n: jnp.ndarray,
                kneg: jax.Array, a: float, b: float, neg_rate: int,
                mode: Optional[str] = None) -> jnp.ndarray:
    """One epoch's per-point SGD delta — the scatter-free epoch body.

    ``layout``/``memb_n`` come from the one-time setup (stable src-sort +
    dst permutation, memberships gathered into layout order).  Attraction
    and repulsion are computed per edge, then reduced into per-point
    deltas by two cumsum-difference segment reductions (src side carries
    attraction + negative samples, dst side the attraction reaction) —
    zero scatter primitives in the jaxpr.  Shared by the optimizer's
    ``fori_loop`` and the throughput bench, so what is timed is exactly
    what runs.
    """
    n = y.shape[0]
    e = layout.src.shape[0]
    # repulsive: neg_rate uniform negatives per edge
    neg = jax.random.randint(kneg, (e, neg_rate), 0, n)
    src_tot, att = _edge_forces(y, layout.src, layout.dst, neg, memb_n,
                                a, b)
    # scatter-free reduction: src side via the src-sorted bounds, dst
    # side (the attraction reaction, −att) via the precomputed gather
    # into dst-sorted order — two O(E) cumsum passes, no .at[].add
    return _reduce_cols(src_tot, layout.src_bounds, mode) \
        - _reduce_cols([c[layout.dst_order] for c in att],
                       layout.dst_bounds, mode)


def _edge_forces(y: jnp.ndarray, src: jnp.ndarray, dst: jnp.ndarray,
                 neg: jnp.ndarray, memb_n: jnp.ndarray, a: float, b: float):
    """Per-edge SGD forces, one 1-D (E,) array per embedding coordinate.

    Returns (src_tot, att): per coordinate, the attraction plus the
    summed negative-sample repulsion on the edge's src point, and the
    attraction alone (its dst point takes the reaction).  ``neg`` is the
    (E, R) negative-sample draw.  Per-edge values stay 1-D (and the
    negatives (R, E)) because a TPU tiles the last dim of an array to 128
    lanes: (E, dims) and (E, R, dims) intermediates would take 32-64x
    their size on the chip.  The arithmetic is element for element the
    (E, dims) form's.
    """
    cols = [y[:, c] for c in range(y.shape[1])]
    diff = [yc[src] - yc[dst] for yc in cols]
    d2 = sum(d * d for d in diff)
    # attractive: dCE/dy = 2ab d^{2(b-1)} / (1 + a d^{2b}) * (ys - yd)
    grad_coef = (-2.0 * a * b * d2 ** (b - 1.0)
                 / (1.0 + a * d2 ** b))
    grad_coef = jnp.where(d2 > 0, grad_coef, 0.0)
    att = [jnp.clip(grad_coef * d, -4.0, 4.0) * memb_n for d in diff]
    # A negative draw can hit the edge's own endpoints — repelling dst
    # would fight the very attraction this edge just applied (src is
    # harmless: zero diff), so those samples are masked out rather than
    # resampled (keeps shapes static; the tiny rate loss matches
    # umap-learn's "skip self" behaviour in expectation).
    neg = neg.T                                            # (R, E)
    valid = (neg != src[None, :]) & (neg != dst[None, :])
    ndiff = [yc[src][None, :] - yc[neg] for yc in cols]    # (R, E) each
    dn2 = sum(d * d for d in ndiff)
    rep_coef = (2.0 * b) / ((0.001 + dn2) * (1.0 + a * dn2 ** b))
    src_tot = [
        t + jnp.sum(jnp.where(valid, jnp.clip(rep_coef * d, -4.0, 4.0)
                              * memb_n[None, :], 0.0), axis=0)
        for t, d in zip(att, ndiff)]
    return src_tot, att


def _reduce_cols(cols, bounds: jnp.ndarray, mode: Optional[str]
                 ) -> jnp.ndarray:
    """Segment-reduce each 1-D per-edge column; (rows, len(cols))."""
    return jnp.stack([coo.segment_reduce(c, bounds, mode=mode)
                      for c in cols], axis=1)


@functools.partial(jax.jit, static_argnames=("cfg", "n"))
def _optimize_embedding_jit(key: jax.Array, edges: jnp.ndarray,
                            memb: jnp.ndarray, n: int, cfg: UmapConfig,
                            init: Optional[jnp.ndarray] = None
                            ) -> jnp.ndarray:
    """Single-device SGD loop (the reference path, fully jitted)."""
    a, b = fit_ab(cfg.spread, cfg.min_dist)
    kinit, kloop = jax.random.split(key)
    y0 = init if init is not None else \
        cfg.init_scale * jax.random.uniform(kinit, (n, cfg.dims)) - \
        cfg.init_scale / 2.0
    layout, order = coo.edge_layout(edges[:, 0], edges[:, 1], n)
    memb_n = (memb / jnp.maximum(jnp.max(memb), 1e-12))[order]

    def epoch(i, state):
        y, key = state
        key, kneg = jax.random.split(key)
        alpha = cfg.learning_rate * (1.0 - i / cfg.n_epochs)
        delta = epoch_delta(y, layout, memb_n, kneg, a, b, cfg.neg_rate,
                            mode=_cfg_kernel_mode(cfg))
        return _OptState(y + alpha * delta, key)

    state = jax.lax.fori_loop(0, cfg.n_epochs, epoch, _OptState(y0, kloop))
    return state.y


def epoch_delta_shard(y_blk: jnp.ndarray, y_full: jnp.ndarray,
                      lay: coo.ShardedEdgeLayout, memb_n: jnp.ndarray,
                      kneg: jax.Array, a: float, b: float, neg_rate: int,
                      n: int, e_total: int, axis: str,
                      mode: Optional[str] = None) -> jnp.ndarray:
    """One epoch's per-point delta for ONE device's row block — the
    shard_map body mirroring :func:`epoch_delta`.

    ``lay``/``memb_n`` are the device's squeezed (Ep,)-slices of the
    row-block layout (``coo.ShardedEdgeLayout``); ``y_full`` the
    all_gathered positions.  Negative samples are drawn as the FULL
    (E, neg_rate) array from the replicated ``kneg`` and gathered by
    ``lay.edge_ids`` — every edge sees bit-identical draws to the
    single-device stream, which is what makes the mesh run draw-for-draw
    reproducible (tests/test_mesh_embed.py).  The src-side reduction is
    local (blocks split at row boundaries); the dst-side attraction
    reaction reduces into a full-length per-block partial and crosses
    devices as ONE ``psum`` — no scatter anywhere.
    """
    neg = jax.random.randint(kneg, (e_total, neg_rate), 0, n)[lay.edge_ids]
    # padded slots carry memb 0, so all their forces are 0
    src_tot, att = _edge_forces(y_full, lay.src, lay.dst, neg, memb_n,
                                a, b)
    src_red = _reduce_cols(src_tot, lay.src_bounds, mode)  # (rows_per, dims)
    dst_part = _reduce_cols([c[lay.dst_order] for c in att],
                            lay.dst_bounds, mode)          # (n_pad, dims)
    dst_tot = jax.lax.psum(dst_part, axis)               # THE dst exchange
    rows_per = lay.src_bounds.shape[0] - 1
    dst_blk = jax.lax.dynamic_slice_in_dim(dst_tot, lay.row_offset,
                                           rows_per, axis=0)
    return src_red - dst_blk


@functools.partial(jax.jit, static_argnames=("cfg", "n", "e_total", "mesh"))
def _optimize_embedding_mesh(key: jax.Array, slay: coo.ShardedEdgeLayout,
                             memb_s: jnp.ndarray,
                             init: Optional[jnp.ndarray], *, cfg: UmapConfig,
                             n: int, e_total: int, mesh) -> jnp.ndarray:
    """Mesh-parallel SGD loop: row blocks of y and contiguous edge slices
    stay on their devices across all epochs; per epoch one all_gather of
    the block positions + one psum of the dst-side partials."""
    a, b = fit_ab(cfg.spread, cfg.min_dist)
    axis = mesh_mod.mesh_axis(mesh)
    n_pad = slay.n_padded
    kinit, kloop = jax.random.split(key)
    if init is None:
        # identical draws to the single-device path, then padded tail rows
        y0 = cfg.init_scale * jax.random.uniform(kinit, (n, cfg.dims)) - \
            cfg.init_scale / 2.0
    else:
        y0 = init
    y0 = jnp.pad(y0, [(0, n_pad - n), (0, 0)])
    P = mesh_mod.P
    lay_specs = jax.tree_util.tree_map(lambda _: P(axis), slay)

    @mesh_mod.shard_map_compat(
        mesh=mesh, in_specs=(P(), lay_specs, P(axis), P(axis)),
        out_specs=P(axis))
    def spmd(key, slay, memb_s, y_blk):
        # (S, ...) leaves arrive as (1, ...) per device — drop the axis
        lay = jax.tree_util.tree_map(lambda x: x[0], slay)
        memb_loc = memb_s[0]

        def epoch(i, state):
            y_blk, key = state
            key, kneg = jax.random.split(key)
            alpha = cfg.learning_rate * (1.0 - i / cfg.n_epochs)
            y_full = jax.lax.all_gather(y_blk, axis, axis=0, tiled=True)
            delta = epoch_delta_shard(y_blk, y_full, lay, memb_loc, kneg,
                                      a, b, cfg.neg_rate, n, e_total, axis,
                                      mode=_cfg_kernel_mode(cfg))
            return _OptState(y_blk + alpha * delta, key)

        state = jax.lax.fori_loop(0, cfg.n_epochs, epoch,
                                  _OptState(y_blk, key))
        return state.y

    return spmd(kloop, slay, memb_s, y0)[:n]


def optimize_embedding(key: jax.Array, edges: jnp.ndarray,
                       memb: jnp.ndarray, n: int, cfg: UmapConfig,
                       init: Optional[jnp.ndarray] = None,
                       mesh=None) -> jnp.ndarray:
    """Epoch-batched SGD on the UMAP cross-entropy, scatter-free.

    Setup builds the bidirectional sorted-COO reduction plan once
    (:func:`repro.core.coo.edge_layout`); every epoch then runs
    :func:`epoch_delta` inside one jitted ``fori_loop`` with zero scatter
    primitives (jaxpr-pinned in tests/test_umap_scatter_free.py).

    With ``mesh`` (``None`` | device count | 1-D ``Mesh``, see
    ``core.mesh``) the loop runs row-block-sharded under ``shard_map``:
    the host slices the src-sorted edge list into per-block contiguous
    shards once (``coo.shard_edge_layout`` — concrete arrays, so this
    path needs ``edges``/``memb`` outside any trace), then every epoch is
    the same math with one all_gather + one psum; negative-sample draws
    stay bit-identical to the single-device stream.
    """
    mesh = mesh_mod.resolve_mesh(mesh)
    if mesh is None:
        return _optimize_embedding_jit(key, edges, memb, n, cfg, init)
    n_shards = mesh_mod.axis_size(mesh, mesh_mod.mesh_axis(mesh))
    # same stable layout order as the reference path, then host-side shard
    layout, order = coo.edge_layout(edges[:, 0], edges[:, 1], n)
    memb_n = (memb / jnp.maximum(jnp.max(memb), 1e-12))[order]
    slay = coo.shard_edge_layout(np.asarray(layout.src),
                                 np.asarray(layout.dst), n, n_shards)
    memb_s = coo.shard_payload(slay, memb_n)
    return _optimize_embedding_mesh(key, slay, memb_s, init, cfg=cfg, n=n,
                                    e_total=int(layout.src.shape[0]),
                                    mesh=mesh)


def run_umap(key: jax.Array, x: jnp.ndarray, cfg: UmapConfig,
             weights: Optional[jnp.ndarray] = None,
             mesh=None, init: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Full UMAP: kNN → fuzzy set → SGD embed.  Returns (N, dims).

    Every stage is memory-bounded: kNN streams ``cfg.block`` rows at a
    time, and symmetrization is sparse — no (N, N) buffer at any N.
    ``mesh`` row-block-shards both the kNN build and the SGD loop under
    ``shard_map`` (see :func:`optimize_embedding`).

    ``init`` seeds the SGD at the given (N, dims) float coordinates
    instead of the uniform cold start — the warm-start hook the online
    service uses to resume from a previous embedding.  Validated for
    shape/dtype; works on the single-device and mesh paths alike."""
    from repro.core.tsne import validate_init
    mesh = mesh_mod.resolve_mesh(mesh)
    init = validate_init(init, x.shape[0], cfg.dims)
    idx, dist = knn_graph(x, cfg.n_neighbors, block=cfg.block, mesh=mesh,
                          method=cfg.knn_method, ann=cfg.ann)
    edges, memb = fuzzy_simplicial_set(idx, dist, weights=weights,
                                       search_iters=cfg.sigma_search_iters)
    return optimize_embedding(key, edges, memb, x.shape[0], cfg, init=init,
                              mesh=mesh)
