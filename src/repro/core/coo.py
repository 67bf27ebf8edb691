"""Shared sorted-COO segment-reduction core for the sparse embedders.

Both sub-quadratic embed engines — the sparse tSNE backend
(``tsne.SparseP``) and the scatter-free UMAP epoch loop
(``umap.optimize_embedding``) — reduce per-edge quantities into per-point
accumulators every optimizer step, over E = O(N·k) fixed-shape COO edges.
The natural primitive is a scatter-add, but XLA's CPU scatter visits
updates one at a time: at E ~ 10⁷ a single ``.at[].add`` costs seconds
where a vectorized pass costs ~100 ms (~100× — measured in
benchmarks/bench_embed_throughput.py).  This module is the scatter-free
alternative both consumers share:

* sort the edge list by the reduction key ONCE at setup (``lexsort`` /
  stable ``argsort``) and precompute the per-row slice boundaries
  (:func:`row_bounds`);
* each step, reduce with :func:`segment_reduce` — an O(E) cumulative sum
  whose per-row totals are differences at the precomputed boundaries.
  Zero scatter primitives appear in the step jaxpr (regression-pinned in
  tests/test_sparse_tsne.py and tests/test_umap_scatter_free.py).

For consumers that must reduce over BOTH endpoints of every edge (UMAP:
the attractive force moves src and dst in opposite directions),
:func:`edge_layout` additionally builds the dst-sorted ordering and the
gather permutation between the two orderings, so the second reduction is
one gather + one more cumsum — still no scatter.

For the mesh-parallel embed stage, :class:`ShardedEdgeLayout` row-block
shards the same machinery: each device owns a contiguous src-row range
(and, because the edge list is src-sorted, a CONTIGUOUS padded slice of
the edge array), runs the identical local cumsum-difference reduction
over its slice, and cross-block dst contributions travel as one ``psum``
of per-block full-length partials — still zero scatter primitives,
per-device (tests/test_mesh_embed.py pins the sharded jaxpr).

Everything here is shape-static and jit-compatible; the sorts live in the
one-time setup, never inside the per-iteration jaxpr.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def row_bounds(sorted_ids: jnp.ndarray, n: int) -> jnp.ndarray:
    """Per-row slice boundaries of a sorted id list: row i owns entries
    [bounds[i], bounds[i+1]).  The invariant every scatter-free cumsum
    reduction in this module builds on."""
    return jnp.searchsorted(sorted_ids,
                            jnp.arange(n + 1)).astype(jnp.int32)


def segment_reduce(vals: jnp.ndarray, bounds: jnp.ndarray,
                   mode: Optional[str] = None) -> jnp.ndarray:
    """Per-row sums of row-sorted per-edge values — WITHOUT scatter.

    ``vals`` is (E,) or (E, D), ordered so that row i's entries occupy
    the contiguous slice [bounds[i], bounds[i+1]) (see :func:`row_bounds`).
    Σ over a row = cumsum difference at the row boundaries: one vectorized
    O(E) pass, versus XLA CPU scatter's serial update walk (~100× slower
    at E ~ 10⁷).  Returns (N,) or (N, D).

    ``mode`` routes through the kernel registry (op ``segment_reduce``):
    None, "auto" and "xla" keep the cumsum-difference path below (auto
    never picks the fused kernel, unless the process pins another mode
    via SNS_KERNEL_MODE / a registry override); "interpret"/"compiled"
    run the fused Pallas kernel (``kernels.segment_reduce``), whose
    compiled tier refuses shapes that exceed its VMEM budget.
    """
    from repro.kernels import registry
    m = registry.resolve_mode(None if mode in (None, "auto") else mode,
                              "segment_reduce")
    if m not in ("auto", "xla"):
        width = 1 if vals.ndim == 1 else vals.shape[1]
        impl = registry.resolve(
            "segment_reduce", mode=m, dtype=vals.dtype,
            shape=(bounds.shape[0] - 1, vals.shape[0], width))
        return impl.fn(vals, bounds,
                       **registry.tile_params("segment_reduce",
                                              shape=vals.shape))
    zero = jnp.zeros((1,) + vals.shape[1:], vals.dtype)
    cs = jnp.concatenate([zero, jnp.cumsum(vals, axis=0)])
    return cs[bounds[1:]] - cs[bounds[:-1]]


class EdgeLayout(NamedTuple):
    """Bidirectional reduction plan over a fixed-shape COO edge list.

    Built once at setup (two sorts), consumed every optimizer step with
    zero scatter primitives:

    * ``src``/``dst`` — the edge list, sorted by ``src`` (stable, so an
      already-src-sorted input keeps its edge order — this is what lets
      per-edge RNG streams line up with the pre-layout reference path);
    * ``src_bounds`` — row slices of the src-sorted order, for reducing
      per-edge values into their SOURCE points via :func:`segment_reduce`;
    * ``dst_order``/``dst_bounds`` — gather permutation into the
      dst-sorted ordering plus its row slices, for reducing the same
      per-edge values into their DESTINATION points:
      ``segment_reduce(vals[dst_order], dst_bounds)``.
    """
    src: jnp.ndarray         # (E,) int32, sorted ascending
    dst: jnp.ndarray         # (E,) int32 (src-sorted edge order)
    src_bounds: jnp.ndarray  # (N+1,) int32
    dst_order: jnp.ndarray   # (E,) int32: edge order -> dst-sorted order
    dst_bounds: jnp.ndarray  # (N+1,) int32


def edge_layout(src: jnp.ndarray, dst: jnp.ndarray, n: int
                ) -> Tuple[EdgeLayout, jnp.ndarray]:
    """Build the bidirectional reduction plan for a COO edge list.

    Returns (layout, order) where ``order`` is the stable src-sort
    permutation applied to the inputs — gather any per-edge payload with
    it once (``memb[order]``) to match the layout's edge order.
    """
    order = jnp.argsort(src, stable=True)
    s = src[order].astype(jnp.int32)
    d = dst[order].astype(jnp.int32)
    dst_order = jnp.argsort(d, stable=True).astype(jnp.int32)
    return EdgeLayout(
        src=s, dst=d,
        src_bounds=row_bounds(s, n),
        dst_order=dst_order,
        dst_bounds=row_bounds(d[dst_order], n)), order


class ShardedEdgeLayout(NamedTuple):
    """Row-block-sharded reduction plan over a src-sorted COO edge list.

    Device s owns the contiguous global row range
    [s·rows_per, (s+1)·rows_per) (``rows_per`` = ``src_bounds.shape[1]−1``;
    the last block may contain padded rows beyond N).  Because the input
    edge list is sorted by src, each block's edges are a CONTIGUOUS slice
    of the global array; slices are padded to the max per-block edge count
    Ep so every leading-axis entry has the same shape and the whole layout
    enters ``shard_map`` with ``P(axis)`` in-specs (device s sees its own
    (Ep,)-rows after squeezing).

    Per-device reduction contract (all scatter-free, see
    tests/test_mesh_embed.py for the jaxpr pin):

    * src side — ``segment_reduce(vals, src_bounds[s])`` over LOCAL row
      ids (``src − s·rows_per``) gives the block's (rows_per, ...) sums;
    * dst side — ``segment_reduce(vals[dst_order[s]], dst_bounds[s])``
      gives a FULL-LENGTH (n_padded, ...) per-block partial over GLOBAL
      dst rows; one ``psum`` over the mesh axis totals the cross-block
      contributions (no cross-device scatter anywhere);
    * padded edge slots repeat the block's last real edge with
      ``edge_mask`` False — gather any payload through
      :func:`shard_payload`, which zeroes them, so they vanish from every
      linear reduction;
    * ``edge_ids`` maps each slot back to its global edge index — the
      hook that keeps per-edge RNG streams draw-for-draw aligned with the
      single-device path (draw globally, gather by ``edge_ids``).
    """
    src: jnp.ndarray         # (S, Ep) int32 global src ids, sorted per block
    dst: jnp.ndarray         # (S, Ep) int32 global dst ids
    edge_ids: jnp.ndarray    # (S, Ep) int32 global edge index of each slot
    edge_mask: jnp.ndarray   # (S, Ep) bool, False on padded slots
    src_bounds: jnp.ndarray  # (S, rows_per+1) int32, LOCAL-row slices
    dst_order: jnp.ndarray   # (S, Ep) int32: block order -> dst-sorted order
    dst_bounds: jnp.ndarray  # (S, n_padded+1) int32, GLOBAL-row slices
    row_offset: jnp.ndarray  # (S,) int32 first global row of each block

    @property
    def n_shards(self) -> int:
        return self.src.shape[0]

    @property
    def rows_per_shard(self) -> int:
        return self.src_bounds.shape[1] - 1

    @property
    def n_padded(self) -> int:
        return self.dst_bounds.shape[1] - 1


def shard_edge_layout(src, dst, n: int, n_shards: int) -> ShardedEdgeLayout:
    """Build the row-block-sharded reduction plan — host-side, setup-time.

    ``src``/``dst`` are the (E,) global edge list, ``src`` sorted
    ascending (the invariant :func:`edge_layout` and ``tsne.SparseP``
    already maintain).  Runs in numpy on concrete arrays: the per-block
    edge counts are data-dependent, so the padded width Ep must be known
    before anything is traced.
    """
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    e = src.shape[0]
    if e and np.any(src[1:] < src[:-1]):
        raise ValueError("shard_edge_layout needs a src-sorted edge list")
    rows_per = -(-n // n_shards)
    n_pad = rows_per * n_shards
    starts = np.searchsorted(src, np.arange(n_shards) * rows_per)
    ends = np.append(starts[1:], e)
    ep = max(1, int(np.max(ends - starts)))

    ids = np.empty((n_shards, ep), np.int64)
    mask = np.empty((n_shards, ep), bool)
    src_b = np.empty((n_shards, rows_per + 1), np.int64)
    dst_b = np.empty((n_shards, n_pad + 1), np.int64)
    dst_o = np.empty((n_shards, ep), np.int64)
    for s in range(n_shards):
        cnt = ends[s] - starts[s]
        # padded slots repeat the block's last real edge (or edge 0 for an
        # empty block): their src stays inside the block, keeping the
        # per-block src-sorted invariant, and shard_payload zeroes them
        last = max(starts[s], ends[s] - 1) if cnt else 0
        row = np.minimum(starts[s] + np.arange(ep), last)
        ids[s] = row
        mask[s] = np.arange(ep) < cnt
        local = src[row] - s * rows_per
        src_b[s] = np.searchsorted(local, np.arange(rows_per + 1))
        order = np.argsort(dst[row], kind="stable")
        dst_o[s] = order
        dst_b[s] = np.searchsorted(dst[row][order], np.arange(n_pad + 1))

    return ShardedEdgeLayout(
        src=jnp.asarray(src[ids], jnp.int32),
        dst=jnp.asarray(dst[ids], jnp.int32),
        edge_ids=jnp.asarray(ids, jnp.int32),
        edge_mask=jnp.asarray(mask),
        src_bounds=jnp.asarray(src_b, jnp.int32),
        dst_order=jnp.asarray(dst_o, jnp.int32),
        dst_bounds=jnp.asarray(dst_b, jnp.int32),
        row_offset=jnp.asarray(np.arange(n_shards) * rows_per, jnp.int32))


def shard_payload(layout: ShardedEdgeLayout, vals: jnp.ndarray
                  ) -> jnp.ndarray:
    """Gather a (E, ...) per-edge payload into the sharded layout's
    (S, Ep, ...) slot order, zeroed on padded slots — padded edges then
    contribute exactly nothing to any linear reduction."""
    out = jnp.asarray(vals)[layout.edge_ids]
    m = layout.edge_mask
    return jnp.where(m.reshape(m.shape + (1,) * (out.ndim - 2)), out, 0)


def dedupe_edges(src: jnp.ndarray, dst: jnp.ndarray, val: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Canonical COO: sort by (src, dst), fold duplicate ordered pairs.

    Returns (src, dst, val) of the same fixed shape (E,), sorted
    lexicographically, where each distinct ordered pair carries its total
    value on the first entry of its run and 0 on the duplicates.  Total
    mass is preserved exactly; downstream segment-sums are unaffected by
    the zeroed duplicate slots, while per-pair quantities (Σ p log p, the
    symmetry check) become well defined.

    Setup-time only (the run-head fold is a segment_sum scatter); the
    per-iteration reductions go through :func:`segment_reduce`.
    """
    e = src.shape[0]
    order = jnp.lexsort((dst, src))
    s, d, v = src[order], dst[order], val[order]
    new_run = jnp.concatenate([
        jnp.ones((1,), bool), (s[1:] != s[:-1]) | (d[1:] != d[:-1])])
    run_id = jnp.cumsum(new_run) - 1
    run_sum = jax.ops.segment_sum(v, run_id, num_segments=e)
    v_out = jnp.where(new_run, run_sum[run_id], 0.0)
    return s, d, v_out
