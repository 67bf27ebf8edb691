"""Sketch-and-Scale end-to-end pipeline (paper Fig. 1).

    1. set a regular grid            → core.quantize.fit_grid[_streaming]
    2. count points, find heavy bins → core.sketch/stream + heavy_hitters
    3. representatives per heavy bin → core.replicas
    4. feed into tSNE / UMAP         → core.tsne / core.umap

Single-host and mesh-distributed front-ends share all stages; only stage 2
differs (local sketch vs. shard_map + psum via core.geo).

Two ingest regimes for stage 1-2:

* one-shot — ``run(cfg, points)`` with the full (N, D) array resident;
* streaming — ``run_streaming(cfg, chunks)`` folds a chunk iterator
  through ``core.stream.IngestState`` (bounded memory, two passes: chunked
  min/max for the grid, then sketch+reservoir).  ``chunks_from_loader``
  adapts a ``data.loader.ShardedLoader`` plan into the re-iterable chunk
  stream this needs.

The two regimes are *equivalent*: on the same data (and a candidate pool
that covers the distinct occupied cells) they produce bit-identical heavy
hitters — tests/test_stream_ingest.py property-tests the contract.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import candidates as cand_mod
from repro.core import geo, heavy_hitters as hh_mod, quantize, replicas
from repro.core import mesh as mesh_mod
from repro.core import sketch as sketch_mod
from repro.core import stream as stream_mod
from repro.core import tsne as tsne_mod
from repro.core import u64
from repro.core import umap as umap_mod
from repro.core.heavy_hitters import HeavyHitters
from repro.core.quantize import GridSpec
from repro.core.replicas import Representatives


@dataclasses.dataclass(frozen=True)
class SnsConfig:
    """Paper-parameterized pipeline config (defaults = cancer experiment)."""
    bins: int = 25                 # M, linear bins per axis
    rows: int = 16                 # R, sketch rows
    log2_cols: int = 18            # C = 2^18 ≈ the paper's 2·10^5
    top_k: int = 20_000            # heavy hitters to extract
    candidate_pool: int = 0        # 0 -> 2*top_k (reservoir size L too)
    ingest_chunk: int = 65_536     # streaming ingest: points per chunk step
    ingest_superbatch: int = 8     # chunks folded per dispatch (1 = off)
    replica_scheme: str = "count"  # "uniform" | "rank" | "count"
    max_replicas: int = 8
    jitter_frac: float = 0.25
    embedder: str = "umap"         # "umap" | "tsne"
    embed_dims: int = 2
    # tSNE gradient: "dense"|"tiled"|"pallas" (exact, O(N²) per iter) or
    # "sparse" (kNN attraction + FFT grid repulsion, O(N·k + G²logG) —
    # the N = 10⁵-10⁶ representative regime)
    embed_backend: str = "dense"
    embed_block: int = 512         # row-block for tiled/pallas tSNE + UMAP kNN
    embed_knn: int = 0             # sparse tSNE: kNN fan-out (0 → 3·perp)
    embed_grid: int = 128          # sparse tSNE: FFT repulsion grid G
    # sparse tSNE adaptive grid: > 0 = target cell spacing (embed_grid
    # becomes the starting G and doubles with the embedding span up to
    # embed_grid_max — FIt-SNE-style, see tsne.TsneConfig.grid_interval)
    embed_grid_interval: float = 0.0
    embed_grid_max: int = 1024
    embed_cic: str = "xla"         # grid splat/gather: "xla" | "pallas"
    # kNN build for BOTH embedders: "exact" (brute force, O(N²·D)),
    # "ann" (sub-quadratic sketch-bucketing + NN-descent, core.ann), or
    # "auto" (exact below ann.AnnConfig.auto_threshold points, ann
    # above — the safe default).  embed_ann optionally carries the
    # recall/probe knobs as an ann.AnnConfig (None = defaults)
    embed_knn_method: str = "auto"
    embed_ann: object = None       # None | ann.AnnConfig
    # mesh-parallel embed stage: None = single device; an int builds a 1-D
    # mesh of that many local devices; a ready jax Mesh passes through.
    # Row-block-shards the kNN build + the whole optimizer loop of BOTH
    # embedders under shard_map (sparse tSNE only — see tsne.run_tsne);
    # collective contract in core.mesh
    embed_mesh: object = None      # None | int | jax.sharding.Mesh
    # Pallas kernel dispatch tier for the embed stage (kernels.registry):
    # "auto" = the registry's pick for the current backend (compiled, else
    # xla, on an accelerator; interpret, else xla, on CPU; the fused
    # segment-reduce only when forced); "compiled"|"interpret"|"xla"
    # force one tier for every registry op (cic splat/gather, tSNE force
    # tile, kNN distance scan, the fused segment-reduce).
    kernel_mode: str = "auto"
    # sites of the deployment (paper §V): SnsService keeps one fold per
    # site, sharded over jax.devices()[:min(sites, n)] (sites dividing
    # evenly across them), and the master merges them; 1 = one fold
    sites: int = 1
    seed: int = 0

    def __post_init__(self):
        """Fail-loud validation: nonsensical values are caught HERE with
        a message naming the knob, instead of surfacing as shape errors
        deep inside a jitted trace."""
        checks = [
            (self.bins >= 2, f"bins (grid M) must be >= 2, got {self.bins}"),
            (self.rows >= 1,
             f"rows (sketch R) must be >= 1 — a zero-row sketch estimates "
             f"nothing; got {self.rows}"),
            (1 <= self.log2_cols <= 31,
             f"log2_cols must be in [1, 31], got {self.log2_cols}"),
            (self.top_k >= 1, f"top_k must be >= 1, got {self.top_k}"),
            (self.candidate_pool >= 0,
             f"candidate_pool must be >= 0 (0 = 2*top_k), "
             f"got {self.candidate_pool}"),
            (self.ingest_chunk >= 1,
             f"ingest_chunk must be >= 1, got {self.ingest_chunk}"),
            (self.ingest_superbatch >= 1,
             f"ingest_superbatch must be >= 1 (1 = off), "
             f"got {self.ingest_superbatch}"),
            (self.replica_scheme in ("uniform", "rank", "count"),
             f"replica_scheme must be 'uniform'|'rank'|'count', "
             f"got {self.replica_scheme!r}"),
            (self.max_replicas >= 1,
             f"max_replicas must be >= 1, got {self.max_replicas}"),
            (0.0 <= self.jitter_frac <= 1.0,
             f"jitter_frac must be in [0, 1] (fraction of a cell), "
             f"got {self.jitter_frac}"),
            (self.embedder in ("umap", "tsne"),
             f"embedder must be 'umap'|'tsne', got {self.embedder!r}"),
            (self.embed_dims >= 1,
             f"embed_dims must be >= 1, got {self.embed_dims}"),
            (self.embed_backend in ("dense", "tiled", "pallas", "sparse"),
             f"embed_backend must be 'dense'|'tiled'|'pallas'|'sparse', "
             f"got {self.embed_backend!r}"),
            (self.embed_block >= 1,
             f"embed_block must be >= 1, got {self.embed_block}"),
            (self.embed_knn >= 0,
             f"embed_knn must be >= 0 (0 = 3*perplexity), "
             f"got {self.embed_knn}"),
            (self.embed_grid >= 2,
             f"embed_grid must be >= 2, got {self.embed_grid}"),
            (self.embed_grid_interval >= 0.0,
             f"embed_grid_interval must be >= 0 (0 = fixed grid), "
             f"got {self.embed_grid_interval}"),
            (self.embed_grid_max >= self.embed_grid,
             f"embed_grid_max ({self.embed_grid_max}) must be >= "
             f"embed_grid ({self.embed_grid})"),
            (self.embed_cic in ("xla", "pallas"),
             f"embed_cic must be 'xla'|'pallas', got {self.embed_cic!r}"),
            (self.embed_knn_method in ("exact", "auto", "ann"),
             f"embed_knn_method must be 'exact'|'auto'|'ann', "
             f"got {self.embed_knn_method!r}"),
            (self.kernel_mode in ("auto", "compiled", "interpret", "xla"),
             f"kernel_mode must be 'auto'|'compiled'|'interpret'|'xla', "
             f"got {self.kernel_mode!r}"),
            (self.sites >= 1, f"sites must be >= 1, got {self.sites}"),
        ]
        bad = [msg for ok, msg in checks if not ok]
        if bad:
            raise ValueError("invalid SnsConfig: " + "; ".join(bad))


@dataclasses.dataclass
class SnsResult:
    grid: GridSpec
    hh: HeavyHitters
    reps: Representatives
    embedding: jnp.ndarray         # (live_reps, embed_dims)
    rep_weight: np.ndarray         # weights of live reps
    rep_hh_id: np.ndarray          # HH index of each live rep
    coverage: float                # fraction of stream mass in the HHs
    # candidate-stage recall diagnostic, measured on every path: the
    # largest exact count ever withheld from the candidate set (reservoir
    # eviction when streaming; local top-L truncation one-shot; pmax over
    # shards on a mesh).  On the resilient path this is WIDENED by the
    # estimated mass of lost shards (resilience.widened_bound).  0.0 =
    # the candidate set provably contains every occupied cell, so the
    # heavy hitters are exact up to the pool size
    hh_error_bound: float = 0.0
    # fraction of the expected stream mass actually observed by ingest:
    # 1.0 everywhere except the resilient path after shard loss, where
    # partial aggregation folds only the shards that delivered
    # (distinct from `coverage`, the HH-mass fraction OF the observed)
    ingest_coverage: float = 1.0
    # shard ids the resilient path lost (empty on every other path)
    lost_shards: Tuple[int, ...] = ()


def _chunk_stream(chunks) -> Iterable:
    """One pass over a chunk source: a callable factory or an iterable."""
    return chunks() if callable(chunks) else iter(chunks)


def _is_points_array(points) -> bool:
    return isinstance(points, (jnp.ndarray, np.ndarray)) or \
        hasattr(points, "shape")


def sketch_stage(cfg: SnsConfig, points,
                 grid: Optional[GridSpec] = None,
                 mesh=None, data_axes=("data",)
                 ) -> Tuple[GridSpec, HeavyHitters]:
    """Stages 1-2: grid + heavy hitters (local or mesh-distributed).

    ``points`` may be a resident (N, D) array (one-shot path) or a chunk
    iterator / factory (single-host streaming path; delegates to
    :func:`sketch_stage_streaming`)."""
    grid, hh, _ = _sketch_stage_impl(cfg, points, grid=grid, mesh=mesh,
                                     data_axes=data_axes)
    return grid, hh


def _sketch_stage_impl(cfg: SnsConfig, points, grid, mesh, data_axes
                       ) -> Tuple[GridSpec, HeavyHitters, float]:
    """Stages 1-2 plus the candidate-stage recall watermark (the third
    return: largest count withheld from the candidate set; 0 = complete)."""
    if not _is_points_array(points):
        if mesh is not None:
            raise ValueError(
                "chunk-iterator input is single-host only; use "
                "geo.geo_extract_from_shards for the mesh streaming path")
        grid, state = _ingest_stream(cfg, points, grid)
        hh = hh_mod.from_candidates(state.sketch, state.cands, cfg.top_k)
        return grid, hh, float(stream_mod.space_saving_bound(state))
    if grid is None:
        grid = quantize.fit_grid(points, cfg.bins)
    if mesh is not None:
        res = geo.geo_extract(
            mesh, grid, points, rows=cfg.rows, log2_cols=cfg.log2_cols,
            top_k=cfg.top_k, candidate_pool=cfg.candidate_pool,
            data_axes=data_axes, seed=cfg.seed)
        return grid, res.hh, float(res.evict_max)
    # fused single-sort path: one sort+RLE feeds the sketch scatter and
    # the candidate top-k alike (same math as update_sorted + extract)
    key_hi, key_lo = quantize.points_to_keys(grid, points)
    sk = sketch_mod.init(jax.random.key(cfg.seed), cfg.rows, cfg.log2_cols)
    runs = cand_mod.sorted_runs(
        key_hi, key_lo,
        assume_hi_zero=grid.dims * grid.bits_per_dim <= 32)
    sk = sketch_mod.update_runs(sk, runs)
    pool = cfg.candidate_pool or min(2 * cfg.top_k, key_hi.shape[0])
    cands, dropped = cand_mod.topk_from_runs(runs, pool,
                                             return_dropped=True)
    hh = hh_mod.from_candidates(sk, cands, cfg.top_k)
    return grid, hh, float(dropped)


def sketch_stage_streaming(cfg: SnsConfig, chunks,
                           grid: Optional[GridSpec] = None,
                           ) -> Tuple[GridSpec, HeavyHitters, float]:
    """Stages 1-2 over a chunk stream, bounded memory.

    ``chunks``: an iterable of (n_i, D) arrays, or a zero-arg callable
    returning one.  When ``grid`` is None two passes are made (chunked
    min/max, then sketch), so the source must be re-iterable — pass a
    callable or a sequence, or supply the grid up front.

    Returns (grid, heavy hitters, total ingested count) — the count comes
    from the ingest state, not from re-materializing the stream."""
    grid, state = _ingest_stream(cfg, chunks, grid)
    hh = hh_mod.from_candidates(state.sketch, state.cands, cfg.top_k)
    return grid, hh, float(state.count)


def _ingest_stream(cfg: SnsConfig, chunks, grid: Optional[GridSpec]
                   ) -> Tuple[GridSpec, stream_mod.IngestState]:
    """Shared ingest fold: grid fit (pass 1 if needed) + fused superbatched
    ingest (pass 2).  Returns the final :class:`stream.IngestState` so
    callers can surface its diagnostics (count, eviction watermark)."""
    if grid is None:
        if not callable(chunks) and iter(chunks) is chunks:
            raise ValueError(
                "grid=None needs two passes over the stream, but `chunks` "
                "is a one-shot iterator; pass a callable / sequence, or "
                "fit the grid up front (quantize.fit_grid_streaming)")
        grid = quantize.fit_grid_streaming(_chunk_stream(chunks), cfg.bins)
    pool = cfg.candidate_pool or 2 * cfg.top_k
    state = stream_mod.init(jax.random.key(cfg.seed), cfg.rows,
                            cfg.log2_cols, pool)
    state = stream_mod.ingest_all(state, grid, _chunk_stream(chunks),
                                  cfg.ingest_chunk,
                                  superbatch=cfg.ingest_superbatch)
    if float(state.count) == 0.0:
        # a factory returning the SAME exhausted iterator passes the
        # re-iterable guard above but yields nothing on the ingest pass —
        # fail loudly instead of returning empty heavy hitters
        raise ValueError(
            "ingest pass saw no data; if `chunks` is a callable it must "
            "return a FRESH iterator on every call")
    return grid, state


def resolve_embed_cfg(cfg: SnsConfig, tsne_cfg=None, umap_cfg=None):
    """Embedder config with SnsConfig's backend/block/kNN knobs applied.

    SnsConfig is authoritative for the embedding backend/block — the
    tsne/umap cfgs carry algorithm hyper-parameters only."""
    # a forced kernel tier also pins the ANN stage-1 distance kernel
    # (AnnConfig.kernel_mode None = defer to its tile/interpret knobs)
    ann_cfg = cfg.embed_ann
    if cfg.kernel_mode != "auto":
        from repro.core import ann as ann_mod
        ann_cfg = dataclasses.replace(ann_cfg or ann_mod.AnnConfig(),
                                      kernel_mode=cfg.kernel_mode)
    if cfg.embedder == "tsne":
        tc = tsne_cfg or tsne_mod.TsneConfig(dims=cfg.embed_dims)
        return dataclasses.replace(tc, backend=cfg.embed_backend,
                                   block=cfg.embed_block, knn=cfg.embed_knn,
                                   grid_size=cfg.embed_grid,
                                   grid_interval=cfg.embed_grid_interval,
                                   grid_max=cfg.embed_grid_max,
                                   cic=cfg.embed_cic,
                                   knn_method=cfg.embed_knn_method,
                                   ann=ann_cfg,
                                   kernel_mode=cfg.kernel_mode)
    if cfg.embedder == "umap":
        # embed_block bounds the kNN row-block on the UMAP side too
        # (tests/test_umap_scatter_free.py pins the propagation)
        uc = umap_cfg or umap_mod.UmapConfig(dims=cfg.embed_dims)
        return dataclasses.replace(uc, block=cfg.embed_block,
                                   knn_method=cfg.embed_knn_method,
                                   ann=ann_cfg,
                                   kernel_mode=cfg.kernel_mode)
    raise ValueError(f"unknown embedder {cfg.embedder!r}")


def embed_points(cfg: SnsConfig, key, x: jnp.ndarray, weights: jnp.ndarray,
                 ecfg=None, *, init: Optional[jnp.ndarray] = None,
                 tsne_cfg=None, umap_cfg=None
                 ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Run the configured embedder on already-built representatives.

    Returns ``(embedding, kl_trace)`` — the trace is the tSNE per-iteration
    KL history, or None for UMAP.  ``init`` (optional (N, dims)) warm-starts
    the optimizer; ``ecfg`` short-circuits :func:`resolve_embed_cfg` for
    callers that pre-resolved the embedder config (the service keeps one
    resolved cold config and a warm variant)."""
    embed_mesh = mesh_mod.resolve_mesh(cfg.embed_mesh)
    if ecfg is None:
        ecfg = resolve_embed_cfg(cfg, tsne_cfg=tsne_cfg, umap_cfg=umap_cfg)
    # only forward init= when set: run_tsne/run_umap stand-ins predating
    # the warm-start hook stay call-compatible
    kw = {} if init is None else {"init": init}
    if cfg.embedder == "tsne":
        emb, kl = tsne_mod.run_tsne(key, x, ecfg, weights=weights,
                                    mesh=embed_mesh, **kw)
        return emb, kl
    emb = umap_mod.run_umap(key, x, ecfg, weights=weights, mesh=embed_mesh,
                            **kw)
    return emb, None


def embed_stage(cfg: SnsConfig, grid: GridSpec, hh: HeavyHitters,
                tsne_cfg: Optional[tsne_mod.TsneConfig] = None,
                umap_cfg: Optional[umap_mod.UmapConfig] = None,
                ) -> Tuple[Representatives, jnp.ndarray, np.ndarray, np.ndarray]:
    """Stages 3-4: replicas + tSNE/UMAP on the live representatives.

    With ``cfg.embed_mesh`` set the embedder runs row-block-sharded under
    ``shard_map`` (see ``core.mesh``); results stay fp-equivalent to the
    single-device path, and UMAP's negative-sample draws stay
    draw-for-draw aligned (tests/test_mesh_embed.py)."""
    key = jax.random.key(cfg.seed + 1)
    krep, kembed = jax.random.split(key)
    reps = replicas.make_representatives(
        krep, grid, hh, scheme=cfg.replica_scheme,
        max_replicas=cfg.max_replicas, jitter_frac=cfg.jitter_frac)
    pts, w, ids = replicas.compact(reps)
    emb, _ = embed_points(cfg, kembed, jnp.asarray(pts), jnp.asarray(w),
                          tsne_cfg=tsne_cfg, umap_cfg=umap_cfg)
    return reps, emb, w, ids


def run(cfg: SnsConfig, points, grid: Optional[GridSpec] = None,
        mesh=None, data_axes=("data",),
        tsne_cfg=None, umap_cfg=None) -> SnsResult:
    """Full SnS: points → embedding of weighted heavy-hitter representatives.

    A chunk iterator / factory instead of an array delegates to
    :func:`run_streaming` (single-host only).  ``mesh`` shards the
    *sketch* stage; ``cfg.embed_mesh`` independently shards the *embed*
    stage (see :func:`embed_stage`) — set both to run the whole pipeline
    under ``shard_map``, as examples/geo_distributed.py does."""
    if not _is_points_array(points):
        if mesh is not None:
            raise ValueError(
                "chunk-iterator input is single-host only; use "
                "run_streaming(mesh=..., shard_fn=...) for the mesh path")
        return run_streaming(cfg, points, grid=grid, tsne_cfg=tsne_cfg,
                             umap_cfg=umap_cfg)
    grid, hh, bound = _sketch_stage_impl(cfg, points, grid=grid, mesh=mesh,
                                         data_axes=data_axes)
    reps, emb, w, ids = embed_stage(cfg, grid, hh, tsne_cfg=tsne_cfg,
                                    umap_cfg=umap_cfg)
    n_total = int(np.prod(points.shape[:-1]))  # all leading dims are batch
    coverage = float(jnp.sum(hh.count) / max(n_total, 1))
    return SnsResult(grid=grid, hh=hh, reps=reps, embedding=emb,
                     rep_weight=w, rep_hh_id=ids, coverage=coverage,
                     hh_error_bound=bound)


def run_streaming(cfg: SnsConfig, chunks=None,
                  grid: Optional[GridSpec] = None,
                  mesh=None, data_axes=("data",),
                  shard_fn=None, num_batches: int = 1,
                  tsne_cfg=None, umap_cfg=None) -> SnsResult:
    """Full SnS over a stream — no stage materializes all N points.

    Single-host: ``chunks`` is an iterable of (n_i, D) arrays or a callable
    factory (re-iterable; needed when ``grid`` is None for the min/max
    pass).  Mesh: pass ``mesh`` + ``shard_fn(idx, batch) -> (points, mask)``
    + ``num_batches`` (see ``geo.geo_extract_from_shards``); ``grid`` is
    then required, since geo-distributed sites must agree on the hypercube
    without a global data pass.

    ``coverage`` is HH mass over the ingest-state's running count — the
    stream length is never re-derived from a resident array.  After
    ingest, ``cfg.embed_mesh`` applies to the embed stage exactly as in
    :func:`run` (the two meshes are independent: a geo ingest mesh can
    hand off to a local embed mesh, or re-use the same devices)."""
    if mesh is not None:
        if shard_fn is None:
            raise ValueError("mesh streaming needs shard_fn + num_batches")
        if grid is None:
            raise ValueError(
                "mesh streaming needs an agreed grid up front (the paper's "
                "shared-hypercube contract); supply grid=")
        res = geo.geo_extract_from_shards(
            mesh, grid, shard_fn, rows=cfg.rows, log2_cols=cfg.log2_cols,
            top_k=cfg.top_k, candidate_pool=cfg.candidate_pool,
            data_axes=data_axes, seed=cfg.seed, num_batches=num_batches)
        hh, total = res.hh, float(res.total_count)
        bound = float(res.evict_max)   # summed per-site watermark
    else:
        if chunks is None:
            raise ValueError("single-host streaming needs a chunk source")
        grid, state = _ingest_stream(cfg, chunks, grid)
        hh = hh_mod.from_candidates(state.sketch, state.cands, cfg.top_k)
        total = float(state.count)
        bound = float(stream_mod.space_saving_bound(state))
    reps, emb, w, ids = embed_stage(cfg, grid, hh, tsne_cfg=tsne_cfg,
                                    umap_cfg=umap_cfg)
    coverage = float(jnp.sum(hh.count)) / max(total, 1.0)
    return SnsResult(grid=grid, hh=hh, reps=reps, embedding=emb,
                     rep_weight=w, rep_hh_id=ids, coverage=coverage,
                     hh_error_bound=bound)


def run_resilient(cfg: SnsConfig, shard_chunks, grid: GridSpec, *,
                  policy=None, deadline: Optional[float] = None,
                  min_coverage: float = 0.0, expected_counts=None,
                  faults=None, tsne_cfg=None, umap_cfg=None) -> SnsResult:
    """Full SnS over independent per-shard chunk sources with failure
    handling — the fault-tolerant front-end of :func:`run_streaming`.

    Each shard folds its own stream into a summary (host-level jobs, not
    one SPMD program), so shards can fail without failing the run:
    transient errors RETRY under ``policy`` (``resilience.RetryPolicy``),
    stragglers are cut off at ``deadline`` seconds, permanent losses
    DEGRADE into partial aggregation — the result carries
    ``ingest_coverage < 1``, the lost shard ids, and an
    ``hh_error_bound`` widened by the estimated lost mass — and coverage
    below ``min_coverage`` FAILS LOUD (``resilience.CoverageError``).
    See ``geo.resilient_extract`` for the collection machinery and
    ``core.faults`` for the reproducible-chaos hook (``faults=``).

    ``grid`` is required up front (the shared-hypercube contract: sites
    that may be lost cannot be part of a global min/max pass)."""
    res = geo.resilient_extract(
        grid, shard_chunks, rows=cfg.rows, log2_cols=cfg.log2_cols,
        top_k=cfg.top_k, candidate_pool=cfg.candidate_pool, seed=cfg.seed,
        chunk_size=cfg.ingest_chunk, superbatch=cfg.ingest_superbatch,
        policy=policy, deadline=deadline, min_coverage=min_coverage,
        expected_counts=expected_counts, faults=faults)
    reps, emb, w, ids = embed_stage(cfg, grid, res.hh, tsne_cfg=tsne_cfg,
                                    umap_cfg=umap_cfg)
    coverage = float(jnp.sum(res.hh.count)) / max(res.observed_count, 1.0)
    return SnsResult(grid=grid, hh=res.hh, reps=reps, embedding=emb,
                     rep_weight=w, rep_hh_id=ids, coverage=coverage,
                     hh_error_bound=res.hh_error_bound,
                     ingest_coverage=res.coverage, lost_shards=res.lost)


def chunks_from_loader(plan, host: int,
                       make_batch: Callable[[int, int], np.ndarray],
                       batches_per_shard: int = 1,
                       steal: bool = False,
                       globally_completed=None,
                       on_shard_done: Optional[Callable[[int], None]] = None,
                       faults=None,
                       on_shard_error: Optional[
                           Callable[[int, Exception], bool]] = None
                       ) -> Callable:
    """Adapt a ``data.loader.ShardPlan`` into the re-iterable chunk factory
    ``run_streaming`` consumes.  Each pass builds a fresh ``ShardedLoader``
    (its ``completed`` set is mutated by iteration, so a loader instance is
    single-use) and yields the raw batch arrays in plan order.

    ``steal=True`` turns on the plan's straggler mitigation: after this
    host drains its primary slice, it calls ``ShardedLoader.steal`` with
    the shards other hosts have already finished (``globally_completed`` —
    a zero-arg callable re-read at steal time, or a static sequence) and
    ingests the leftovers in the plan's deterministic steal order.
    ``on_shard_done(shard)`` fires once per shard AFTER its last batch is
    yielded — the hook a multi-host driver uses to publish completions to
    whatever shared board backs ``globally_completed``.  Hosts that share
    one board process every shard exactly once between them
    (tests/test_loader.py::test_chunks_from_loader_steals_exactly_once).

    Fault tolerance: ``faults`` (a ``core.faults.FaultPlan``) wraps
    ``make_batch`` with reproducible chaos, and ``on_shard_error(shard,
    exc) -> bool`` decides a failing shard's fate — return True to skip
    it (the loader records it in ``ShardedLoader.failed``, its batches
    are withheld all-or-nothing, and ingest degrades to the surviving
    shards), False/None to re-raise (fail loud).  Skipped shards are NOT
    marked completed, so a shared board leaves them for another host's
    steal pass to rescue.

    Caveat: with ``grid=None`` the pipeline iterates the factory twice
    (min/max pass, then ingest) while the board keeps moving — supply the
    grid up front so only the single ingest pass claims shards.
    """
    from repro.data.loader import ShardedLoader

    if faults is not None:
        from repro.core import faults as faults_mod
        make_batch = faults_mod.chaos_make_batch(faults, make_batch)

    def factory():
        loader = ShardedLoader(plan, host, make_batch,
                               batches_per_shard=batches_per_shard,
                               on_error=on_shard_error)

        def drain(pairs):
            prev = None
            for shard, batch in pairs:
                if prev is not None and shard != prev \
                        and on_shard_done is not None:
                    on_shard_done(prev)
                prev = shard
                yield batch
            if prev is not None and on_shard_done is not None:
                on_shard_done(prev)

        yield from drain(iter(loader))
        if steal:
            done = globally_completed() if callable(globally_completed) \
                else (globally_completed or ())
            yield from drain(loader.steal(done))
    return factory


@functools.partial(jax.jit, static_argnames=("grid", "chunk"))
def _assign_chunks(pts: jnp.ndarray, shi: jnp.ndarray, slo: jnp.ndarray,
                   sids: jnp.ndarray, grid: GridSpec, chunk: int
                   ) -> jnp.ndarray:
    """Quantize + binary-search ``pts`` (padded to a chunk multiple)
    against the sorted HH key table, one ``lax.map`` chunk at a time —
    peak memory O(chunk), one compile per (grid, chunk, shapes)."""
    nk = shi.shape[0]

    def one(p):
        khi, klo = quantize.points_to_keys(grid, p)
        pos = u64.searchsorted((shi, slo), (khi, klo))
        pos_c = jnp.minimum(pos, nk - 1)
        hit = (shi[pos_c] == khi) & (slo[pos_c] == klo)
        return jnp.where(hit, sids[pos_c], -1)

    nb = pts.shape[0] // chunk
    return jax.lax.map(one, pts.reshape(nb, chunk, -1)).reshape(-1)


def assign_points_to_hh(grid: GridSpec, hh: HeavyHitters,
                        points: jnp.ndarray, chunk: int = 65536
                        ) -> np.ndarray:
    """Label raw points by nearest HH cell key (-1 if not an HH cell).

    Used to project HH-level cluster labels back to the raw data, as the
    paper does for the contingency table (§IV-1), and by the service's
    drift accounting.  The whole per-chunk body (quantize + two-limb
    binary search, :func:`repro.core.u64.searchsorted`) is one jitted
    ``lax.map`` — no per-chunk host round-trip, so large query batches
    stream at device speed."""
    n = points.shape[0]
    hh_hi = np.asarray(hh.key_hi, np.uint64)
    hh_lo = np.asarray(hh.key_lo, np.uint64)
    live = np.asarray(hh.mask).astype(bool)
    packed = (hh_hi << np.uint64(32)) | hh_lo
    order = np.argsort(packed[live], kind="stable")
    sorted_keys = packed[live][order]
    sorted_ids = np.flatnonzero(live)[order]
    if sorted_keys.size == 0 or n == 0:
        return np.full((n,), -1, np.int64)
    shi = jnp.asarray((sorted_keys >> np.uint64(32)).astype(np.uint32))
    slo = jnp.asarray(sorted_keys.astype(np.uint32))
    sids = jnp.asarray(sorted_ids.astype(np.int32))
    chunk = max(1, min(int(chunk), n))
    pts = np.asarray(points, np.float32)
    pad = (-n) % chunk
    if pad:
        pts = np.concatenate([pts, np.zeros((pad, pts.shape[1]),
                                            np.float32)])
    out = _assign_chunks(jnp.asarray(pts), shi, slo, sids, grid, chunk)
    return np.asarray(out[:n]).astype(np.int64)
