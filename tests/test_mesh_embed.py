"""Mesh-parallel embed stage: sharded layout, equivalence, jaxpr contract.

Two tiers:

* host-side tests (any device count) — ``coo.shard_edge_layout`` property
  tests against ``np.add.at``, ``core.mesh`` sizing helpers, dispatch
  guards, and the pipeline wiring of ``SnsConfig.embed_mesh``;
* 8-device tests (skipped unless the process sees >= 8 devices) — the
  fp-equivalence and collective-contract pins for the sharded kNN build,
  sparse tSNE iteration, and UMAP epoch loop.  CI runs this file as a
  separate step under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
  (the flag must be set before jax initializes; the main test process
  keeps seeing 1 device per the project's dry-run discipline), and the
  slow subprocess wrapper at the bottom gives the default suite the same
  coverage.

The equivalence contract is deliberately split by horizon: per-step
quantities (gradients, epoch deltas) agree to tight fp tolerance, and
short optimizer prefixes agree to loose tolerance — but BOTH embedders'
dynamics are chaotic (momentum+gains sign switches, near-singular UMAP
repulsion), so summation-order noise from the block-local reductions is
amplified exponentially and end-state equality over hundreds of steps is
not a well-posed contract.  Long-horizon agreement is asserted at the
quality level instead (final KL within a few percent).
"""
import dataclasses
import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from benchmarks.common import count_primitive
from repro.core import coo, pipeline, tsne, umap
from repro.core import mesh as mesh_mod

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


# ---------------------------------------------------- host-side: row blocks
def test_row_block_sizing():
    assert mesh_mod.row_block(16, 4) == (4, 16)
    assert mesh_mod.row_block(17, 4) == (5, 20)      # non-dividing: padded
    assert mesh_mod.row_block(3, 8) == (1, 8)        # more shards than rows
    rows_per, n_pad = mesh_mod.row_block(203, 8)
    assert n_pad >= 203 and n_pad == rows_per * 8


def test_resolve_mesh_normalizes_specs():
    assert mesh_mod.resolve_mesh(None) is None
    m = mesh_mod.resolve_mesh(1)
    assert isinstance(m, mesh_mod.Mesh)
    assert mesh_mod.mesh_axis(m) == mesh_mod.EMBED_AXIS
    assert mesh_mod.axis_size(m, mesh_mod.EMBED_AXIS) == 1
    assert mesh_mod.resolve_mesh(m) is m             # Mesh passes through
    with pytest.raises(TypeError):
        mesh_mod.resolve_mesh("eight")
    with pytest.raises(ValueError):
        mesh_mod.make_embed_mesh(jax.device_count() + 1)


@given(seed=st.integers(0, 2**31 - 1), n=st.integers(2, 80),
       e=st.integers(1, 400), s=st.integers(1, 9))
@settings(max_examples=30, deadline=None)
def test_shard_edge_layout_reduces_like_np_add_at(seed, n, e, s):
    """Property: over arbitrary src-sorted COO multisets (duplicate edges,
    rows with no edges, empty blocks, block counts that don't divide N),
    the per-block local src reduction stitched back together == np.add.at
    on src, and the psum of per-block full-length dst partials ==
    np.add.at on dst."""
    rng = np.random.default_rng(seed)
    src = np.sort(rng.integers(0, n, e)).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    vals = rng.normal(size=(e, 2)).astype(np.float32)
    lay = coo.shard_edge_layout(src, dst, n, s)
    v = coo.shard_payload(lay, jnp.asarray(vals))     # (S, Ep, 2)

    rows_per, n_pad = lay.rows_per_shard, lay.n_padded
    assert lay.n_shards == s and n_pad == rows_per * s >= n
    # payload on padded slots is exactly zero
    assert float(jnp.abs(jnp.where(lay.edge_mask[..., None], 0.0, v)
                         ).max()) == 0.0
    # every live slot maps back to its global edge (draw-alignment hook)
    ids = np.asarray(lay.edge_ids)
    mask = np.asarray(lay.edge_mask)
    np.testing.assert_array_equal(np.asarray(lay.src)[mask], src[ids[mask]])
    np.testing.assert_array_equal(np.sort(ids[mask]), np.arange(e))

    by_src = np.concatenate([
        np.asarray(coo.segment_reduce(v[b], lay.src_bounds[b]))
        for b in range(s)])                           # (n_pad, 2)
    dst_parts = [coo.segment_reduce(jnp.asarray(v[b])[lay.dst_order[b]],
                                    lay.dst_bounds[b]) for b in range(s)]
    by_dst = np.asarray(sum(dst_parts))               # the psum, host-side

    ref_src = np.zeros((n_pad, 2), np.float64)
    ref_dst = np.zeros((n_pad, 2), np.float64)
    np.add.at(ref_src, src, vals.astype(np.float64))
    np.add.at(ref_dst, dst, vals.astype(np.float64))
    scale = max(1.0, np.abs(ref_src).max(), np.abs(ref_dst).max())
    assert np.abs(by_src - ref_src).max() <= 1e-4 * scale
    assert np.abs(by_dst - ref_dst).max() <= 1e-4 * scale


def test_shard_edge_layout_rejects_unsorted_src():
    with pytest.raises(ValueError, match="sorted"):
        coo.shard_edge_layout(np.array([3, 1]), np.array([0, 0]), 4, 2)


def test_run_tsne_mesh_requires_sparse_backend():
    x = jnp.zeros((8, 3))
    cfg = tsne.TsneConfig(backend="dense", n_iter=1)
    with pytest.raises(ValueError, match="sparse"):
        tsne.run_tsne(jax.random.key(0), x, cfg, mesh=1)


# ---------------------------------------------------- host-side: wiring
def test_embed_stage_wires_embed_mesh_into_both_embedders(monkeypatch):
    """SnsConfig.embed_mesh (an int spec) must reach run_umap/run_tsne as
    a resolved 1-D Mesh."""
    seen = {}

    def fake_run_umap(key, x, cfg, weights=None, mesh=None):
        seen["umap"] = mesh
        return jnp.zeros((x.shape[0], cfg.dims))

    def fake_run_tsne(key, x, cfg, weights=None, backend=None, mesh=None):
        seen["tsne"] = mesh
        return jnp.zeros((x.shape[0], cfg.dims)), jnp.zeros((cfg.n_iter,))

    monkeypatch.setattr(pipeline.umap_mod, "run_umap", fake_run_umap)
    monkeypatch.setattr(pipeline.tsne_mod, "run_tsne", fake_run_tsne)
    rng = np.random.default_rng(0)
    pts = jnp.asarray(rng.uniform(0, 1, size=(256, 3)).astype(np.float32))
    for embedder in ("umap", "tsne"):
        cfg = pipeline.SnsConfig(bins=8, rows=4, log2_cols=10, top_k=32,
                                 embedder=embedder, embed_mesh=1,
                                 embed_backend="sparse")
        grid, hh = pipeline.sketch_stage(cfg, pts)
        pipeline.embed_stage(cfg, grid, hh)
    for k in ("umap", "tsne"):
        assert isinstance(seen[k], mesh_mod.Mesh), k
        assert mesh_mod.mesh_axis(seen[k]) == mesh_mod.EMBED_AXIS


# ------------------------------------------------------- 8-device fixtures
def _blob_data(n=203, dims=5, seed=0):
    """Two-cluster weighted data at a deliberately non-dividing N."""
    rng = np.random.default_rng(seed)
    x = np.concatenate([rng.normal(0, 1, (n // 2, dims)),
                        rng.normal(6, 1, (n - n // 2, dims))])
    w = rng.integers(1, 50, n).astype(np.float32)
    return jnp.asarray(x.astype(np.float32)), jnp.asarray(w)


@pytest.fixture(scope="module")
def mesh8():
    return mesh_mod.make_embed_mesh(8)


# ------------------------------------------------ host-side: kNN edge cases
def test_knn_graph_block_not_dividing_n_matches_dense():
    """Blocked exact path at a block that does NOT divide N (203 = 5·37
    + 18): padded tail rows must not leak into anyone's neighbor list."""
    from repro.core import neighbors
    x, _ = _blob_data()
    i1, d1 = neighbors.knn_graph(x, 10)
    i2, d2 = neighbors.knn_graph(x, 10, block=37)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=1e-5)


@pytest.mark.parametrize("method", ["exact", "ann"])
def test_knn_graph_clamps_k_to_n_minus_1(method):
    """k ≥ N−1 clamps to N−1 on every path (a point has at most N−1
    neighbors), and with k = N−1 both engines return the full sorted
    neighbor set — so they must agree exactly."""
    from repro.core import neighbors
    x, _ = _blob_data(n=9)
    idx, dist = neighbors.knn_graph(x, 50, method=method)
    assert idx.shape == (9, 8) and dist.shape == (9, 8)
    own = np.arange(9)[:, None]
    assert not (np.asarray(idx) == own).any()        # never lists itself
    ei, _ = neighbors.knn_graph(x, 8)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ei))


# ----------------------------------------------------- 8-device: kNN + grad
@needs8
def test_knn_graph_mesh_matches_single_device(mesh8):
    from repro.core import neighbors
    x, _ = _blob_data()
    i1, d1 = neighbors.knn_graph(x, 10, block=64)
    i2, d2 = neighbors.knn_graph(x, 10, block=64, mesh=mesh8)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=1e-5)


@needs8
def test_ann_knn_graph_mesh_matches_single_device(mesh8):
    """The approximate engine under shard_map at a non-power-of-two N is
    BIT-exact vs single-device: replicated probe merges, per-global-row
    RNG draws, and a psum'd change count make the sharded NN-descent take
    the identical trajectory (a layout/draw misalignment would diverge in
    round 1)."""
    from repro.core import neighbors
    x, _ = _blob_data()
    i1, d1 = neighbors.knn_graph(x, 10, method="ann")
    i2, d2 = neighbors.knn_graph(x, 10, method="ann", mesh=mesh8)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


@needs8
def test_sharded_tsne_gradient_matches_sparse_grad(mesh8):
    """Per-iteration quantities agree tightly: the sharded gradient, KL,
    and Z are the same math reassociated over blocks."""
    x, w = _blob_data()
    n = x.shape[0]
    axis = mesh_mod.mesh_axis(mesh8)
    P = mesh_mod.P
    sp = tsne.build_sparse_p(x, 10.0, k=10, weights=w)
    ssp = tsne.shard_sparse_p(sp, n, 8)
    rows_per, n_pad = mesh_mod.row_block(n, 8)
    rng = np.random.default_rng(1)
    y = jnp.asarray(rng.normal(0, 1e-2, (n, 2)).astype(np.float32))
    yp = jnp.pad(y, [(0, n_pad - n), (0, 0)])
    g_ref, kl_ref = tsne.sparse_grad(y, sp, 12.0, grid_size=32)

    lay_specs = jax.tree_util.tree_map(lambda _: P(axis), ssp)

    @mesh_mod.shard_map_compat(mesh=mesh8, in_specs=(P(axis), lay_specs, P()),
                               out_specs=(P(axis), P()))
    def spmd(y_blk, ssp_, y_full):
        lay = jax.tree_util.tree_map(lambda a: a[0], ssp_.layout)
        return tsne.sparse_grad_shard(y_blk, lay, ssp_.val[0], y_full,
                                      12.0, 32, axis, n)

    g_mesh, kl_mesh = spmd(yp, ssp, yp)
    scale = max(1.0, float(jnp.abs(g_ref).max()))
    assert float(jnp.abs(g_ref - g_mesh[:n]).max()) <= 1e-4 * scale
    # padded rows must receive exactly zero gradient
    assert float(jnp.abs(g_mesh[n:]).max()) == 0.0
    assert abs(float(kl_ref) - float(kl_mesh)) <= 1e-3


@needs8
@pytest.mark.parametrize("grid_interval", [0.0, 0.5])
def test_run_tsne_mesh_matches_single_device_prefix(mesh8, grid_interval):
    """Short optimizer prefix (both the fixed-G and the adaptive staged
    drivers): same key, same config → same trajectory to fp tolerance at
    a non-dividing N."""
    x, w = _blob_data()
    cfg = tsne.TsneConfig(backend="sparse", n_iter=8, grid_size=32, knn=10,
                          grid_interval=grid_interval, grid_max=64,
                          adaptive_interval=4,
                          exaggeration_iters=5, momentum_switch=5)
    key = jax.random.key(3)
    y1, k1 = tsne.run_tsne(key, x, cfg, weights=w)
    y2, k2 = tsne.run_tsne(key, x, cfg, weights=w, mesh=mesh8)
    assert y2.shape == y1.shape
    scale = max(1.0, float(jnp.abs(y1).max()))
    assert float(jnp.abs(y1 - y2).max()) <= 2e-2 * scale
    np.testing.assert_allclose(np.asarray(k1), np.asarray(k2), atol=1e-2)


@needs8
def test_run_tsne_mesh_long_run_stays_stable_and_descends(mesh8):
    """Long horizon: trajectories decohere (chaotic dynamics amplify
    block-reduction fp noise through the momentum+gains optimizer), and
    on a 203-point landscape the two runs legitimately settle in
    different basins — so the contract here is STABILITY, not closeness:
    the sharded run must stay finite for 150 iterations and descend into
    the same quality regime as the single-device run.  (The tight
    equivalence contracts live in the per-gradient and short-prefix
    tests above.)"""
    x, w = _blob_data()
    # learning_rate tamed for this tiny heavily-weighted blob (the
    # default 200 diverges on BOTH paths), and quality read as the best
    # post-exaggeration KL: the late gains build-up overshoots the
    # funnel floor, so the final iterate is noise, the floor is not
    cfg = tsne.TsneConfig(backend="sparse", n_iter=150, grid_size=32,
                          knn=10, exaggeration_iters=40, momentum_switch=40,
                          learning_rate=20.0)
    key = jax.random.key(5)
    _, k1 = tsne.run_tsne(key, x, cfg, weights=w)
    _, k2 = tsne.run_tsne(key, x, cfg, weights=w, mesh=mesh8)
    k1, k2 = np.asarray(k1), np.asarray(k2)
    assert np.isfinite(k1).all() and np.isfinite(k2).all()
    q1, q2 = float(k1[45:].min()), float(k2[45:].min())
    # both optimizers descended well below the post-exaggeration start...
    assert q1 < 0.7 * float(k1[45]) and q2 < 0.7 * float(k2[45])
    # ...into the same quality regime (different basins differ by tens of
    # percent on this toy landscape; a broken collective would be orders)
    assert max(q1, q2) <= 2.5 * min(q1, q2), (q1, q2)


# ------------------------------------------------------- 8-device: UMAP
@needs8
def test_run_umap_mesh_matches_single_device_prefix(mesh8):
    """Short optimizer prefix, draw-for-draw: any negative-sample
    misalignment would produce O(1) differences after a single epoch, so
    the tight epoch-1 tolerance doubles as the RNG alignment test."""
    x, w = _blob_data()
    for epochs, tol in ((1, 1e-4), (3, 2e-2)):
        cfg = umap.UmapConfig(n_epochs=epochs, n_neighbors=10, block=64)
        u1 = umap.run_umap(jax.random.key(7), x, cfg, weights=w)
        u2 = umap.run_umap(jax.random.key(7), x, cfg, weights=w, mesh=mesh8)
        assert u2.shape == u1.shape
        scale = max(1.0, float(jnp.abs(u1).max()))
        assert float(jnp.abs(u1 - u2).max()) <= tol * scale, epochs


@needs8
def test_umap_mesh_epoch_delta_matches_reference(mesh8):
    """The sharded per-epoch delta == the single-device epoch_delta for
    the same key at every state along a short trajectory."""
    x, w = _blob_data(n=117)
    n = x.shape[0]
    axis = mesh_mod.mesh_axis(mesh8)
    P = mesh_mod.P
    cfg = umap.UmapConfig(n_neighbors=8, block=64)
    a, b = umap.fit_ab(cfg.spread, cfg.min_dist)
    idx, dist = umap.knn_graph(x, cfg.n_neighbors, block=cfg.block)
    edges, memb = umap.fuzzy_simplicial_set(idx, dist, weights=w)
    layout, order = coo.edge_layout(edges[:, 0], edges[:, 1], n)
    memb_n = (memb / jnp.maximum(jnp.max(memb), 1e-12))[order]
    slay = coo.shard_edge_layout(np.asarray(layout.src),
                                 np.asarray(layout.dst), n, 8)
    memb_s = coo.shard_payload(slay, memb_n)
    e_total = int(layout.src.shape[0])
    rows_per, n_pad = mesh_mod.row_block(n, 8)
    lay_specs = jax.tree_util.tree_map(lambda _: P(axis), slay)

    @mesh_mod.shard_map_compat(
        mesh=mesh8, in_specs=(P(axis), lay_specs, P(axis), P()),
        out_specs=P(axis))
    def spmd(y_blk, slay_, memb_s_, kneg):
        lay = jax.tree_util.tree_map(lambda v: v[0], slay_)
        y_full = jax.lax.all_gather(y_blk, axis, axis=0, tiled=True)
        return umap.epoch_delta_shard(y_blk, y_full, lay, memb_s_[0], kneg,
                                      a, b, cfg.neg_rate, n, e_total, axis)

    rng = np.random.default_rng(2)
    y = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
    kloop = jax.random.key(11)
    for i in range(4):
        kloop, kneg = jax.random.split(kloop)
        ref = umap.epoch_delta(y, layout, memb_n, kneg, a, b, cfg.neg_rate)
        yp = jnp.pad(y, [(0, n_pad - n), (0, 0)])
        got = spmd(yp, slay, memb_s, kneg)
        scale = max(1.0, float(jnp.abs(ref).max()))
        assert float(jnp.abs(ref - got[:n]).max()) <= 1e-4 * scale, i
        assert float(jnp.abs(got[n:]).max()) == 0.0    # padded rows inert
        y = y + 0.7 * ref


# ------------------------------------------------- 8-device: jaxpr contract
@needs8
def test_sharded_tsne_stage_jaxpr_pins_collectives_and_scatters(mesh8):
    """The sharded iteration adds ZERO scatter primitives over the
    single-device stage (the only scatter-adds are the same four CIC
    corner splats, now per device) and speaks exactly the documented
    collective set: one all_gather (block positions) + five psums (grid,
    Z, two KL partials, centering mean)."""
    x, w = _blob_data()
    n = x.shape[0]
    cfg = tsne.TsneConfig(backend="sparse", n_iter=4, grid_size=32, knn=10)
    sp = tsne.build_sparse_p(x, cfg.perplexity, k=10, weights=w)
    ssp = tsne.shard_sparse_p(sp, n, 8)
    rows_per, n_pad = mesh_mod.row_block(n, 8)
    kls = jnp.zeros((cfg.n_iter,))
    it0 = jnp.asarray(0, jnp.int32)

    def state(rows):
        z = jnp.zeros((rows, 2))
        return tsne.TsneState(z, z, jnp.ones((rows, 2)))

    sharded = jax.make_jaxpr(functools.partial(
        tsne._sparse_stage_mesh, cfg=cfg, count=4, grid_size=32,
        mesh=mesh8, n=n))(state(n_pad), kls, ssp, it0)
    single = jax.make_jaxpr(functools.partial(
        tsne._sparse_stage, cfg=cfg, count=4, grid_size=32))(
            state(n), kls, sp, it0)

    for prim in ("scatter-add", "scatter", "scatter-mul", "scatter-max"):
        assert count_primitive(sharded.jaxpr, prim) == \
            count_primitive(single.jaxpr, prim), \
            f"sharding changed {prim} count"
    assert count_primitive(sharded.jaxpr, "scatter-add") == 4  # CIC corners
    assert count_primitive(sharded.jaxpr, "all_gather") == 1
    assert count_primitive(sharded.jaxpr, "psum") == 5
    for prim in ("ppermute", "all_to_all", "reduce_scatter"):
        assert count_primitive(sharded.jaxpr, prim) == 0


@needs8
def test_sharded_umap_optimizer_jaxpr_scatter_free_and_pinned(mesh8):
    """The whole sharded UMAP optimizer (setup + epoch fori_loop): zero
    scatter primitives of any flavour, and exactly one all_gather (block
    positions) + one psum (dst-side partials) per epoch body."""
    x, w = _blob_data()
    n = x.shape[0]
    cfg = umap.UmapConfig(n_epochs=3, n_neighbors=10, block=64)
    idx, dist = umap.knn_graph(x, cfg.n_neighbors, block=cfg.block)
    edges, memb = umap.fuzzy_simplicial_set(idx, dist, weights=w)
    layout, order = coo.edge_layout(edges[:, 0], edges[:, 1], n)
    memb_n = (memb / jnp.maximum(jnp.max(memb), 1e-12))[order]
    slay = coo.shard_edge_layout(np.asarray(layout.src),
                                 np.asarray(layout.dst), n, 8)
    memb_s = coo.shard_payload(slay, memb_n)
    jaxpr = jax.make_jaxpr(functools.partial(
        umap._optimize_embedding_mesh, cfg=cfg, n=n,
        e_total=int(layout.src.shape[0]), mesh=mesh8))(
            jax.random.key(0), slay, memb_s, None)
    for prim in ("scatter-add", "scatter", "scatter-mul", "scatter-max"):
        assert count_primitive(jaxpr.jaxpr, prim) == 0, prim
    assert count_primitive(jaxpr.jaxpr, "all_gather") == 1
    assert count_primitive(jaxpr.jaxpr, "psum") == 1
    for prim in ("ppermute", "all_to_all", "reduce_scatter"):
        assert count_primitive(jaxpr.jaxpr, prim) == 0


@needs8
def test_cancer_1m_config_constructs_sharded_stage(mesh8):
    """CANCER_1M smoke: derive the TsneConfig exactly as embed_stage does
    and CONSTRUCT (trace, not run) the sharded adaptive stage at the
    paper's grid/knn settings — the full-scale run is a benchmark, but
    the trace must already be valid here."""
    from repro.configs.sns_paper import CANCER_1M
    tc = tsne.TsneConfig(dims=CANCER_1M.embed_dims)
    tc = dataclasses.replace(
        tc, backend=CANCER_1M.embed_backend, block=CANCER_1M.embed_block,
        knn=CANCER_1M.embed_knn, grid_size=CANCER_1M.embed_grid,
        grid_interval=CANCER_1M.embed_grid_interval,
        grid_max=CANCER_1M.embed_grid_max, cic=CANCER_1M.embed_cic)
    assert tc.backend == "sparse" and tc.grid_interval > 0
    # modest row count; the static structure (G, staged adaptive driver,
    # collective set) is what the trace checks
    n = 512
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n, 8)).astype(np.float32))
    sp = tsne.build_sparse_p(x, tc.perplexity, k=tc.knn or None,
                             block=tc.block)
    ssp = tsne.shard_sparse_p(sp, n, 8)
    rows_per, n_pad = mesh_mod.row_block(n, 8)
    z = jnp.zeros((n_pad, 2))
    state = tsne.TsneState(z, z, jnp.ones((n_pad, 2)))
    jaxpr = jax.make_jaxpr(functools.partial(
        tsne._sparse_stage_mesh, cfg=tc, count=tc.adaptive_interval,
        grid_size=tc.grid_size, mesh=mesh8, n=n))(
            state, jnp.zeros((tc.n_iter,)), ssp, jnp.asarray(0, jnp.int32))
    assert count_primitive(jaxpr.jaxpr, "all_gather") == 1
    assert count_primitive(jaxpr.jaxpr, "psum") == 5


# ------------------------------------------------- 8-device: full pipeline
@needs8
def test_pipeline_embed_mesh_end_to_end_matches_single_device(mesh8):
    """SnsConfig.embed_mesh end to end (sketch → HH → reps → sharded
    UMAP): same result as the single-device pipeline to fp tolerance."""
    rng = np.random.default_rng(4)
    pts = jnp.asarray(rng.uniform(0, 1, size=(4096, 3)).astype(np.float32))
    base = dict(bins=8, rows=4, log2_cols=10, top_k=64, embedder="umap")
    ucfg = umap.UmapConfig(n_epochs=2, n_neighbors=8)
    cfg1 = pipeline.SnsConfig(**base)
    cfg2 = pipeline.SnsConfig(**base, embed_mesh=mesh8)
    r1 = pipeline.run(cfg1, pts, umap_cfg=ucfg)
    r2 = pipeline.run(cfg2, pts, umap_cfg=ucfg)
    assert r1.embedding.shape == r2.embedding.shape
    scale = max(1.0, float(jnp.abs(r1.embedding).max()))
    assert float(jnp.abs(r1.embedding - r2.embedding).max()) <= 1e-3 * scale


# ---------------------------------------------------- subprocess tier bridge
@pytest.mark.slow
def test_mesh_suite_under_virtual_8_devices():
    """Run this file's 8-device tests in a subprocess that actually sees 8
    virtual CPU devices (the default suite's process must keep seeing 1 —
    dry-run discipline), so `pytest -m slow` covers the mesh contract
    without the CI-only XLA_FLAGS step."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-m", "not slow", os.path.abspath(__file__)],
        env=env, capture_output=True, text=True, timeout=3000, cwd=root)
    assert out.returncode == 0, f"STDOUT:{out.stdout}\nSTDERR:{out.stderr}"
