"""Fused single-sort ingest: equivalence, invariants, trace regressions.

The PR-3 throughput refactor replaced the two-sort chunk fold
(``sketch.update_sorted`` + ``candidates.merge_topk`` re-sorting pool ∪
raw-chunk) with one ``candidates.sorted_runs`` per chunk feeding both the
sketch scatter (``sketch.update_runs``) and a sorted-merge reservoir
update (``candidates.merge_runs``, key-sorted carried invariant, two
stable sorts over the reservoir and the runs).  The fused path is a
re-association of the same exact-integer adds, so it must be
*bit-identical* to the legacy path:

* sketch tables equal exactly;
* reservoir live (key → count) sets equal exactly (storage order differs
  by design: merge_topk count-descending vs merge_runs key-ascending);
* heavy hitters extracted from either reservoir equal exactly.

Plus the trace regressions the perf claims rest on: ONE sort of the
chunk's keys per chunk step (legacy had two), a reservoir merge built from
exactly two stable sorts with no gather and no binary-search loop (on a
TPU v5e a 2^20-element sort costs ~0.8 ms, the searchsorted gathers it
replaced ~37 ms a chunk), and the superbatched scan's trace is O(1) in the
number of stacked chunks.  And two follow-ups of the fused fold:
resumable ingest (save/load round-trip, bit-identical resume) and the
eviction-watermark space-saving diagnostic.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import candidates, pipeline, quantize, sketch as sketch_mod
from repro.core import stream
from repro.core.candidates import Candidates

GRID = quantize.GridSpec(dims=3, bins=8, lo=(0.0,) * 3, hi=(1.0,) * 3)
# 6 dims x 6 bits = 36 > 32 bits: keys spill into the hi limb, so the
# general two-limb sort path runs (GRID packs 9 bits -> single-limb path)
GRID_WIDE = quantize.GridSpec(dims=6, bins=64, lo=(0.0,) * 6, hi=(1.0,) * 6)


def legacy_ingest_step(state, grid, points, mask=None):
    """The PR-2 two-sort chunk fold, reconstructed as the reference:
    update_sorted re-sorts the chunk keys, merge_topk re-sorts pool ∪ raw
    chunk.  (stream.ingest_step used to be exactly this.)"""
    pool = state.cands.capacity
    n = points.shape[0]
    key_hi, key_lo = quantize.points_to_keys(grid, points)
    sk = sketch_mod.update_sorted(state.sketch, key_hi, key_lo, mask=mask)
    chunk_cands = Candidates(
        key_hi=key_hi, key_lo=key_lo,
        count=jnp.ones((n,), jnp.float32),
        mask=jnp.ones((n,), bool) if mask is None else mask)
    cands = state.cands.merge_topk(chunk_cands, pool)
    inc = jnp.full((), n, jnp.float32) if mask is None \
        else jnp.sum(mask.astype(jnp.float32))
    return stream.IngestState(sketch=sk, cands=cands,
                              count=state.count + inc,
                              evict_max=state.evict_max)


def _cand_dict(c):
    """Live (packed key) -> count, order-insensitive."""
    m = np.asarray(c.mask)
    hi = np.asarray(c.key_hi, np.uint64)[m]
    lo = np.asarray(c.key_lo, np.uint64)[m]
    cnt = np.asarray(c.count)[m]
    return dict(zip(((hi << np.uint64(32)) | lo).tolist(), cnt.tolist()))


def _assert_key_sorted(c):
    """The merge_runs carried invariant: live keys strictly ascending,
    padding (mask False) only after every live entry."""
    m = np.asarray(c.mask)
    live_idx = np.flatnonzero(m)
    assert live_idx.size == 0 or live_idx[-1] == live_idx.size - 1, \
        "padding interleaved with live entries"
    packed = (np.asarray(c.key_hi, np.uint64)[m] << np.uint64(32)) | \
        np.asarray(c.key_lo, np.uint64)[m]
    assert np.all(np.diff(packed.astype(np.int64)) > 0), \
        "live keys not strictly ascending"


def _key_stream(rng, n, universe):
    """uint32 keys drawn from `universe` distinct values (dup-heavy when
    universe << n, all-distinct when universe is None)."""
    if universe is None:
        lo = rng.permutation(n).astype(np.uint32)
    else:
        lo = rng.integers(0, universe, size=n).astype(np.uint32)
    hi = (lo % 3).astype(np.uint32)     # exercise the hi limb too
    return jnp.asarray(hi), jnp.asarray(lo)


# ----------------------------------------------------- runs-level identity
@pytest.mark.parametrize("universe,masked_tail", [
    (12, 0), (12, 57), (None, 0), (None, 31), (1, 0)])
def test_update_runs_matches_update_sorted(universe, masked_tail):
    rng = np.random.default_rng(3)
    n = 200
    hi, lo = _key_stream(rng, n, universe)
    mask = jnp.arange(n) < (n - masked_tail)
    sk0 = sketch_mod.init(jax.random.key(0), 4, 8)
    ref = sketch_mod.update_sorted(sk0, hi, lo, mask=mask)
    runs = candidates.sorted_runs(hi, lo, mask=mask)
    fused = sketch_mod.update_runs(sk0, runs)
    np.testing.assert_array_equal(np.asarray(ref.table),
                                  np.asarray(fused.table))


@pytest.mark.parametrize("universe,masked_tail", [
    (10, 0), (10, 40), (300, 0), (None, 0), (None, 25)])
def test_merge_runs_matches_merge_topk(universe, masked_tail):
    """Fold 4 chunks through both reservoir merges: identical live sets
    with bit-identical counts at every step; merge_runs stays key-sorted."""
    rng = np.random.default_rng(7)
    n, pool = 100, 16
    ref = candidates.empty(pool)
    fused = candidates.empty(pool)
    for _ in range(4):
        hi, lo = _key_stream(rng, n, universe)
        mask = jnp.arange(n) < (n - masked_tail)
        chunk = Candidates(key_hi=hi, key_lo=lo,
                           count=jnp.ones((n,), jnp.float32), mask=mask)
        ref = candidates.merge_topk(ref, chunk, pool)
        runs = candidates.sorted_runs(hi, lo, mask=mask)
        fused, _ = candidates.merge_runs(fused, runs, pool)
        _assert_key_sorted(fused)
        assert _cand_dict(ref) == _cand_dict(fused)


@given(universe=st.one_of(st.none(), st.integers(1, 400)),
       masked_tail=st.integers(0, 99), seed=st.integers(0, 2 ** 16))
@settings(max_examples=25, deadline=None)
def test_merge_runs_matches_merge_topk_property(universe, masked_tail, seed):
    rng = np.random.default_rng(seed)
    n, pool = 100, 12
    hi, lo = _key_stream(rng, n, universe)
    mask = jnp.arange(n) < (n - masked_tail)
    start = candidates.local_topk(*_key_stream(rng, 50, 8), pool)
    # merge_runs requires the key-sorted invariant — re-sort the seed pool
    srt = np.argsort(
        (np.asarray(start.key_hi, np.uint64) << np.uint64(32))
        | np.asarray(start.key_lo, np.uint64), kind="stable")
    start_sorted = Candidates(*(jnp.asarray(np.asarray(f)[srt])
                                for f in start))
    chunk = Candidates(key_hi=hi, key_lo=lo,
                       count=jnp.ones((n,), jnp.float32), mask=mask)
    ref = candidates.merge_topk(start, chunk, pool)
    fused, evicted = candidates.merge_runs(
        start_sorted, candidates.sorted_runs(hi, lo, mask=mask), pool)
    _assert_key_sorted(fused)
    assert _cand_dict(ref) == _cand_dict(fused)
    assert float(evicted) >= 0.0


def _sorted_pool(keys, counts, k):
    """A key-sorted reservoir of capacity k holding (packed key → count)."""
    keys = np.asarray(keys, np.uint64)
    order = np.argsort(keys, kind="stable")
    keys, counts = keys[order], np.asarray(counts, np.float32)[order]
    pad = k - keys.size
    return Candidates(
        key_hi=jnp.asarray(np.concatenate([
            (keys >> np.uint64(32)).astype(np.uint32),
            np.full(pad, candidates.INVALID_KEY, np.uint32)])),
        key_lo=jnp.asarray(np.concatenate([
            (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32),
            np.full(pad, candidates.INVALID_KEY, np.uint32)])),
        count=jnp.asarray(np.concatenate([counts,
                                          np.zeros(pad, np.float32)])),
        mask=jnp.asarray(np.arange(k) < keys.size))


def _numpy_merge(pool, hi, lo, mask, k):
    """Exact reference of one reservoir merge: live (key → count) of pool
    plus the chunk's masked counts; keep the k largest counts, ties to the
    lower key; return them key-ascending and the largest evicted count."""
    tot = _cand_dict(pool)
    packed = (np.asarray(hi, np.uint64) << np.uint64(32)) | \
        np.asarray(lo, np.uint64)
    for key in packed[np.asarray(mask)].tolist():
        tot[key] = tot.get(key, 0.0) + 1.0
    ranked = sorted(tot.items(), key=lambda kv: (-kv[1], kv[0]))
    kept = sorted(ranked[:k])
    evicted = max((c for _, c in ranked[k:]), default=0.0)
    return kept, evicted


@pytest.mark.parametrize("case", [
    "empty_pool", "masked_chunk", "ties_at_threshold", "pool_below_runs",
    "run_equals_pool_max"])
def test_merge_runs_edge_cases(case):
    """Edge cases of the reservoir merge against ``merge_topk`` and an
    exact NumPy reference, down to the storage order and the evicted
    maximum: the output stays key-sorted."""
    rng = np.random.default_rng(29)
    n, k = 64, 16
    pool = candidates.empty(k)
    hi, lo = _key_stream(rng, n, 24)
    mask = jnp.ones((n,), bool)
    if case == "masked_chunk":          # a live pool, nothing arrives
        pool = _sorted_pool([3, 9, (1 << 32) + 5], [4.0, 1.0, 2.0], k)
        mask = jnp.zeros((n,), bool)
    elif case == "ties_at_threshold":   # 64 distinct keys of count 1
        hi, lo = _key_stream(rng, n, None)
        pool = _sorted_pool([1000, 1001], [1.0, 1.0], k)
    elif case == "pool_below_runs":     # k = 5 ≪ the chunk's runs
        k = 5
        pool = _sorted_pool([2, 7, 11], [3.0, 1.0, 2.0], k)
        hi, lo = _key_stream(rng, 256, 40)
        n = 256
        mask = jnp.arange(n) < 200
    elif case == "run_equals_pool_max":  # chunk hits the pool's last live
        pool = _sorted_pool([1, 4, 20], [2.0, 1.0, 5.0], k)
        lo = jnp.asarray(np.r_[np.full(8, 20), rng.integers(0, 30, n - 8)]
                         .astype(np.uint32))
        hi = jnp.zeros((n,), jnp.uint32)
    runs = candidates.sorted_runs(hi, lo, mask=mask)
    fused, evicted = candidates.merge_runs(pool, runs, k)
    _assert_key_sorted(fused)
    chunk = Candidates(key_hi=hi, key_lo=lo,
                       count=jnp.ones((n,), jnp.float32), mask=mask)
    assert _cand_dict(fused) == _cand_dict(
        candidates.merge_topk(pool, chunk, k))
    kept, ref_evicted = _numpy_merge(pool, hi, lo, mask, k)
    m = np.asarray(fused.mask)
    assert m.sum() == len(kept) and np.all(m[:len(kept)])
    packed = (np.asarray(fused.key_hi, np.uint64) << np.uint64(32)) | \
        np.asarray(fused.key_lo, np.uint64)
    assert packed[m].tolist() == [key for key, _ in kept]
    assert np.asarray(fused.count)[m].tolist() == [c for _, c in kept]
    assert np.all(np.asarray(fused.key_hi)[~m] == candidates.INVALID_KEY)
    assert np.all(np.asarray(fused.count)[~m] == 0.0)
    assert float(evicted) == ref_evicted


def test_merge_runs_rejects_k_beyond_inputs():
    """The merge keeps at most pool + runs entries: a larger k is refused
    at trace time instead of returning a short reservoir."""
    runs = candidates.sorted_runs(*_key_stream(np.random.default_rng(1),
                                               8, 4))
    with pytest.raises(ValueError, match="exceeds pool"):
        candidates.merge_runs(candidates.empty(4), runs, 13)


# ----------------------------------------------------- step-level identity
@pytest.mark.parametrize("universe,masked_tail,grid", [
    (6, 0, GRID), (6, 100, GRID), (2000, 0, GRID), (None, 0, GRID),
    (None, 64, GRID), (None, 0, GRID_WIDE), (6, 100, GRID_WIDE)])
def test_fused_step_matches_legacy_two_sort_step(universe, masked_tail,
                                                 grid):
    """Full fold over 5 chunks: sketch tables bit-identical, reservoir
    live sets bit-identical, extracted heavy hitters bit-identical —
    on both the single-limb (≤ 32-bit grid) and two-limb key sort paths."""
    from repro.core import heavy_hitters as hh_mod
    rng = np.random.default_rng(11)
    n, pool, k = 256, 64, 32
    st_fused = stream.init(jax.random.key(0), 4, 10, pool)
    st_legacy = stream.init(jax.random.key(0), 4, 10, pool)
    for _ in range(5):
        pts = jnp.asarray(rng.uniform(0, 1, size=(n, grid.dims)),
                          jnp.float32)
        if universe is not None:      # collapse points onto few cells
            pts = jnp.round(pts * (universe % 7 + 2)) / (universe % 7 + 2)
        mask = jnp.arange(n) < (n - masked_tail)
        st_fused = stream.ingest_step(st_fused, grid, pts, mask=mask)
        st_legacy = legacy_ingest_step(st_legacy, grid, pts, mask=mask)
    np.testing.assert_array_equal(np.asarray(st_fused.sketch.table),
                                  np.asarray(st_legacy.sketch.table))
    assert _cand_dict(st_fused.cands) == _cand_dict(st_legacy.cands)
    assert float(st_fused.count) == float(st_legacy.count)
    hh_f = hh_mod.from_candidates(st_fused.sketch, st_fused.cands, k)
    hh_l = hh_mod.from_candidates(st_legacy.sketch, st_legacy.cands, k)
    for a, b in zip(hh_f, hh_l):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------- jaxpr regressions
def _jaxpr_of_step():
    state = stream.init(jax.random.key(0), 4, 8, 16)

    def step(st, pts, mask):
        return stream.ingest_step(st, GRID, pts, mask=mask)

    return jax.make_jaxpr(step)(state, jnp.zeros((512, 3)),
                                jnp.ones((512,), bool))


def _merge_jaxpr(assume_hi_zero):
    """jaxpr of ``merge_runs`` alone, on runs from the one-limb or the
    two-limb key sort."""
    hi, lo = _key_stream(np.random.default_rng(0), 512, 40)
    runs = candidates.sorted_runs(hi, lo, assume_hi_zero=assume_hi_zero)
    return jax.make_jaxpr(lambda p, r: candidates.merge_runs(p, r, 16))(
        candidates.empty(16), runs)


def test_exactly_one_sort_per_chunk_step():
    """The fused step sorts the chunk's keys exactly once (``sorted_runs``;
    the legacy two-sort step: two); every other sort of the step is one
    of ``merge_runs``' two stable sorts, so the step holds three."""
    from benchmarks.common import count_primitive
    step_sorts = count_primitive(_jaxpr_of_step().jaxpr, "sort")
    merge_sorts = count_primitive(_merge_jaxpr(False).jaxpr, "sort")
    assert merge_sorts == 2
    assert step_sorts - merge_sorts == 1

    state = stream.init(jax.random.key(0), 4, 8, 16)

    def legacy(st, pts, mask):
        return legacy_ingest_step(st, GRID, pts, mask=mask)

    legacy_jaxpr = jax.make_jaxpr(legacy)(
        state, jnp.zeros((512, 3)), jnp.ones((512,), bool))
    assert count_primitive(legacy_jaxpr.jaxpr, "sort") == 2


def test_superbatch_trace_o1_and_single_sort():
    """The (B, chunk, D) superbatch scan body is traced once: total
    equation count is independent of B, and the whole superbatch jaxpr
    holds three sorts, whatever B: the chunk's single key sort plus the
    reservoir merge's two."""
    from benchmarks.common import count_eqns, count_primitive

    def jaxpr_for(b):
        state = stream.init(jax.random.key(0), 4, 8, 16)
        return jax.make_jaxpr(
            lambda s, p, m: stream.ingest_superbatch(s, p, m, grid=GRID))(
                state, jnp.zeros((b, 256, 3)), jnp.ones((b, 256), bool))

    j2, j16 = jaxpr_for(2), jaxpr_for(16)
    assert count_eqns(j2.jaxpr) == count_eqns(j16.jaxpr)
    assert count_primitive(j16.jaxpr, "sort") == 3


@pytest.mark.parametrize("assume_hi_zero", [True, False])
def test_merge_runs_is_two_sorts_no_gather_no_while(assume_hi_zero):
    """The reservoir merge carries its payload through two stable
    multi-operand sorts: no gather and no binary-search loop, on both
    the one-limb and the two-limb key paths."""
    from benchmarks.common import count_primitive
    j = _merge_jaxpr(assume_hi_zero).jaxpr
    assert count_primitive(j, "sort") == 2
    assert count_primitive(j, "gather") == 0
    assert count_primitive(j, "while") == 0


def test_superbatch_matches_per_chunk_ingest():
    """ingest_all(superbatch=B) ≡ ingest_all(superbatch=1) bit-exactly,
    including a ragged tail that pads the last superbatch with fully
    masked chunks."""
    rng = np.random.default_rng(5)
    pts = rng.uniform(0, 1, size=(3333, 3)).astype(np.float32)

    def run(superbatch):
        state = stream.init(jax.random.key(1), 4, 10, 64)
        return stream.ingest_all(state, GRID, [pts], 512,
                                 superbatch=superbatch)

    a, b = run(1), run(4)
    np.testing.assert_array_equal(np.asarray(a.sketch.table),
                                  np.asarray(b.sketch.table))
    assert _cand_dict(a.cands) == _cand_dict(b.cands)
    assert float(a.count) == float(b.count) == 3333.0
    assert float(a.evict_max) == float(b.evict_max)


# --------------------------------------------------------- resumable ingest
def test_save_load_resume_bit_identical(tmp_path):
    """Checkpoint mid-stream, reload, finish: heavy hitters bit-identical
    to the uninterrupted fold — including through reservoir evictions
    (pool 64 << 512 occupied cells).  The checkpoint lands on a rechunk
    block boundary (chunk lengths are multiples of 512), so the resumed
    fold sees the exact same block sequence as the straight one."""
    from repro.core import heavy_hitters as hh_mod
    rng = np.random.default_rng(9)
    chunks = [rng.uniform(0, 1, size=(1024, 3)).astype(np.float32)
              for _ in range(6)]

    straight = stream.init(jax.random.key(2), 4, 10, 64)
    straight = stream.ingest_all(straight, GRID, chunks, 512, superbatch=2)

    first = stream.init(jax.random.key(2), 4, 10, 64)
    first = stream.ingest_all(first, GRID, chunks[:3], 512, superbatch=2)
    # suffix-less on purpose: np.savez appends '.npz', load must follow
    path = tmp_path / "ingest_ckpt"
    stream.save_state(first, path)
    resumed = stream.load_state(path)
    resumed = stream.ingest_all(resumed, GRID, chunks[3:], 512, superbatch=2)

    for a, b in zip(jax.tree_util.tree_leaves(straight),
                    jax.tree_util.tree_leaves(resumed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    hh_s = hh_mod.from_candidates(straight.sketch, straight.cands, 32)
    hh_r = hh_mod.from_candidates(resumed.sketch, resumed.cands, 32)
    for a, b in zip(hh_s, hh_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------- eviction watermark
def test_evict_watermark_zero_while_exact():
    """Distinct keys ≤ pool: no eviction ever, watermark stays 0 — the
    reservoir is provably exact."""
    rng = np.random.default_rng(13)
    pts = (rng.integers(0, 3, size=(2000, 3)) / 4.0).astype(np.float32)
    state = stream.init(jax.random.key(0), 4, 10, 64)  # 27 cells << 64
    state = stream.ingest_all(state, GRID, [pts], 256, superbatch=2)
    assert float(stream.space_saving_bound(state)) == 0.0


def test_evict_watermark_rises_on_overflow():
    """More distinct keys than the pool: evictions must happen and the
    watermark records the largest evicted exact count (≤ the heaviest
    key's true count, > 0)."""
    rng = np.random.default_rng(17)
    pts = rng.uniform(0, 1, size=(4000, 3)).astype(np.float32)
    state = stream.init(jax.random.key(0), 4, 10, 8)   # pool 8 << 512 cells
    state = stream.ingest_all(state, GRID, [pts], 256, superbatch=2)
    bound = float(stream.space_saving_bound(state))
    assert bound > 0.0
    assert bound <= float(jnp.max(state.cands.count))


def test_oneshot_and_mesh_surface_watermark():
    """The candidate-stage watermark is measured on every extraction
    path: one-shot local truncation, mesh one-shot (pmax), and the mesh
    streaming reservoir — 0 exactly when the candidate set is complete."""
    from repro.core import geo
    rng = np.random.default_rng(23)
    pts = jnp.asarray(rng.uniform(0, 1, size=(4000, 3)), jnp.float32)
    grid = quantize.fit_grid(pts, 8)    # ~512 occupied cells

    # one-shot run(): tiny pool truncates, big pool does not
    tight = pipeline.SnsConfig(bins=8, rows=4, log2_cols=10, top_k=8,
                               candidate_pool=16, max_replicas=1)
    roomy = pipeline.SnsConfig(bins=8, rows=4, log2_cols=10, top_k=600,
                               candidate_pool=600, max_replicas=1)
    from repro.core.umap import UmapConfig
    ucfg = UmapConfig(n_neighbors=3, n_epochs=2)
    assert pipeline.run(tight, pts, umap_cfg=ucfg).hh_error_bound > 0.0
    assert pipeline.run(roomy, pts, umap_cfg=ucfg).hh_error_bound == 0.0

    # mesh paths (1-device mesh): one-shot pmax + streaming reservoir
    mesh = jax.make_mesh((1,), ("data",))
    res = geo.geo_extract(mesh, grid, pts, rows=4, log2_cols=10,
                          top_k=8, candidate_pool=16)
    assert float(res.evict_max) > 0.0

    def shard_fn(idx, b):
        return pts[b * 500 + jnp.arange(500)], None

    res_s = geo.geo_extract_from_shards(
        mesh, grid, shard_fn, rows=4, log2_cols=10, top_k=8,
        candidate_pool=16, num_batches=8)
    assert float(res_s.evict_max) > 0.0
    res_roomy = geo.geo_extract_from_shards(
        mesh, grid, shard_fn, rows=4, log2_cols=10, top_k=600,
        candidate_pool=600, num_batches=8)
    assert float(res_roomy.evict_max) == 0.0


def test_run_streaming_surfaces_error_bound():
    rng = np.random.default_rng(19)
    pts = rng.uniform(0, 1, size=(3000, 3)).astype(np.float32)
    from repro.core.umap import UmapConfig
    cfg = pipeline.SnsConfig(bins=4, rows=8, log2_cols=10, top_k=32,
                             candidate_pool=96, ingest_chunk=512,
                             ingest_superbatch=2, max_replicas=2)
    res = pipeline.run_streaming(cfg, [pts],
                                 umap_cfg=UmapConfig(n_neighbors=5,
                                                     n_epochs=5))
    # bins=4, D=3 → ≤ 64 occupied cells < pool 96: reservoir exact
    assert res.hh_error_bound == 0.0
