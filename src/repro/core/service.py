"""Online SnS service: the pipeline as a long-lived serving system.

ROADMAP item 3's gap between a reproduction and a production service:
the paper's premise is data that never stops arriving at edge nodes, yet
``pipeline.run`` recomputes everything from a cold start.  The sketch is
linear and the reservoir resumable (PR 2/3), so an :class:`SnsService`
keeps one live :class:`~repro.core.stream.IngestState` and serves three
operations, each a distinct perf lever:

* :meth:`SnsService.update` — fold new chunks into the live fold via the
  fused ``ingest_superbatch`` path.  No re-read of history: absorbing a
  chunk costs the same whether the service has seen 10⁴ or 10⁹ points.
  Heavy hitters are NOT re-extracted here; :meth:`SnsService.needs_refresh`
  watches drift (fraction of mass ingested since the last refresh) and
  the space-saving error watermark against the smallest served HH count.

* :meth:`SnsService.refresh` — re-extract HH → representatives → embed.
  Returning representatives are matched to the previous embedding by
  (quantized cell key, replica slot) and seeded at their old coordinates;
  new cells are placed by inverse-distance-weighted kNN interpolation
  over the matched ones; the optimizer then runs from that init (the
  ``init=`` hooks on ``run_tsne``/``run_umap``) with early exaggeration
  skipped and ~10× fewer iterations than cold start.

* :meth:`SnsService.transform` — batched out-of-sample embedding of raw
  query points with NO optimizer: asymmetric kNN of queries against the
  frozen representative set (:func:`repro.core.neighbors.knn_query`),
  then barycentric placement under inverse-square-distance attraction
  weights — one jitted ``lax.map`` over fixed-size chunks, so peak memory
  is O(chunk · N_reps), never (Q, N_reps), and high query traffic serves
  at batched-millisecond latency.

* Several sites (``SnsConfig.sites``, paper §V): one fold per site,
  held on a leading (sites,) axis of the state and sharded one site per
  device where there are enough (``geo.site_mesh``).  ``update()``
  takes one chunk source per site and folds each on its own device in
  one dispatch, with no collective; :meth:`SnsService.merge` is the
  master's merge (``geo.merge_sites``), whose view refresh and so
  transform read; the error bound sums the sites' watermarks as the
  merge does.  ``save``/``load``/``update_shards``
  keep one fold and refuse a service of several sites.

The grid is fixed at construction (the paper's shared-hypercube
contract): cell keys — the identity that the warm-start matching relies
on — are only comparable across refreshes under one grid.

Failure semantics (what retries, what degrades, what fails loud):

* :meth:`SnsService.update_shards` ingests per-shard sources through the
  resilience collector: transient shard failures RETRY under a
  ``RetryPolicy``, stragglers are cut off at a deadline, permanent
  losses DEGRADE into partial aggregation (the service keeps serving;
  ``health()`` reports ``coverage < 1`` and the widened error bound),
  and coverage under ``min_coverage`` FAILS LOUD without touching the
  live fold.
* :meth:`SnsService.refresh` is TRANSACTIONAL: the new snapshot is built
  entirely off to the side and swapped in atomically; any exception
  mid-refresh leaves the previous snapshot serving (``transform()``
  never observes a half-built state) and is recorded in ``health()``
  before re-raising.
* :meth:`SnsService.save` writes atomically (temp + rename) with a
  checksum and rotates the previous generation to a ``.bak``;
  :meth:`SnsService.load` verifies the checksum and falls back to that
  previous good generation if the newest checkpoint is torn or bit-rotted.
* Calling :meth:`transform` / :meth:`save` before the first refresh
  raises :class:`ServiceNotReadyError` (a ``ValueError``) — never an
  attribute or shape error from deep inside a trace.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core import geo
from repro.core import heavy_hitters as hh_mod
from repro.core import neighbors, pipeline, replicas
from repro.core import resilience
from repro.core import stream as stream_mod
from repro.core.pipeline import SnsConfig
from repro.core.quantize import GridSpec


class ServiceNotReadyError(ValueError):
    """transform()/save() called before the first successful refresh()."""


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Serving-side knobs (the pipeline knobs stay on ``SnsConfig``)."""
    # refresh policy: refresh when the mass ingested since the last
    # refresh exceeds this fraction of the total stream...
    refresh_drift: float = 0.1
    # ...or when the space-saving eviction watermark (the largest count
    # the candidate stage may have withheld) reaches this fraction of the
    # smallest HH count currently being served — past that, the served
    # top-K set itself is in doubt
    error_ratio: float = 0.5
    # warm refresh iteration budget; 0 → cold budget // warm_factor
    warm_iters: int = 0
    warm_factor: int = 10
    # transform(): kNN fan-out, chunk rows per jitted map step, and the
    # attraction weight floor w = 1/(d² + eps) — eps small enough that an
    # identity query (d = 0) collapses onto its representative
    transform_k: int = 8
    transform_chunk: int = 4096
    transform_eps: float = 1e-12

    def __post_init__(self):
        bad = []
        if not 0.0 <= self.refresh_drift <= 1.0:
            bad.append(f"refresh_drift={self.refresh_drift} (need [0, 1])")
        if self.error_ratio < 0:
            bad.append(f"error_ratio={self.error_ratio} (need >= 0)")
        if self.warm_iters < 0:
            bad.append(f"warm_iters={self.warm_iters} (need >= 0)")
        if self.warm_factor < 1:
            bad.append(f"warm_factor={self.warm_factor} (need >= 1)")
        if self.transform_k < 1:
            bad.append(f"transform_k={self.transform_k} (need >= 1)")
        if self.transform_chunk < 1:
            bad.append(f"transform_chunk={self.transform_chunk} "
                       "(need >= 1)")
        if not self.transform_eps > 0:
            bad.append(f"transform_eps={self.transform_eps} (need > 0)")
        if bad:
            raise ValueError("invalid ServiceConfig: " + "; ".join(bad))


@dataclasses.dataclass
class EmbedCache:
    """The frozen serving snapshot produced by the last refresh()."""
    rep_cell: np.ndarray      # (live,) uint64 packed quantized cell key
    rep_slot: np.ndarray      # (live,) int32 replica slot within the cell
    rep_x: jnp.ndarray        # (live, D) representative data coords
    rep_y: jnp.ndarray        # (live, dims) embedded coords
    rep_w: np.ndarray         # (live,) weights (HH counts)
    rep_ids: np.ndarray       # (live,) HH index of each rep
    min_hh_count: float       # smallest served HH count (error_ratio gate)


@dataclasses.dataclass
class RefreshResult:
    embedding: jnp.ndarray    # (live, dims)
    weights: np.ndarray       # (live,)
    hh_ids: np.ndarray        # (live,)
    warm: bool                # did this refresh run from a warm init?
    n_matched: int            # reps seeded at their previous coordinates
    n_new: int                # reps placed by kNN interpolation
    n_iters: int              # optimizer iterations this refresh ran
    kl_trace: Optional[jnp.ndarray]  # tSNE per-iteration KL (None: UMAP)


@functools.partial(jax.jit, static_argnames=("k", "chunk", "eps"))
def _transform_chunks(q: jnp.ndarray, rep_x: jnp.ndarray,
                      rep_y: jnp.ndarray, k: int, chunk: int, eps: float
                      ) -> jnp.ndarray:
    """Barycentric out-of-sample placement, one chunk at a time.

    ``lax.map`` over (nb, chunk, D) keeps the distance buffer at
    (chunk, N_reps) — the jaxpr never allocates (Q, N_reps)
    (tests/test_service.py pins this on the traced avals)."""
    def one(qc):
        idx, dist = neighbors.knn_query(qc, rep_x, k)
        w = 1.0 / (dist * dist + eps)
        w = w / jnp.sum(w, axis=1, keepdims=True)
        return jnp.einsum("qk,qkd->qd", w, rep_y[idx])

    nb = q.shape[0] // chunk
    out = jax.lax.map(one, q.reshape(nb, chunk, -1))
    return out.reshape(-1, rep_y.shape[1])


def _packed_cells(hh: hh_mod.HeavyHitters, ids: np.ndarray) -> np.ndarray:
    """uint64 packed cell key of each live rep (by its HH index)."""
    hi = np.asarray(hh.key_hi, np.uint64)[ids]
    lo = np.asarray(hh.key_lo, np.uint64)[ids]
    return (hi << np.uint64(32)) | lo


class SnsService:
    """Long-lived SnS pipeline: incremental ingest, warm re-embed,
    batched out-of-sample transform.  See the module docstring for the
    serving model; ``examples/sns_service.py`` walks the full loop."""

    def __init__(self, cfg: SnsConfig, grid: GridSpec, *,
                 tsne_cfg=None, umap_cfg=None,
                 service_cfg: Optional[ServiceConfig] = None):
        self.cfg = cfg
        self.grid = grid
        self.scfg = service_cfg or ServiceConfig()
        self._ecfg = pipeline.resolve_embed_cfg(cfg, tsne_cfg=tsne_cfg,
                                                umap_cfg=umap_cfg)
        pool = cfg.candidate_pool or 2 * cfg.top_k
        self.sites = cfg.sites
        self.mesh = None
        if self.sites == 1:
            self.state = stream_mod.init(jax.random.key(cfg.seed), cfg.rows,
                                         cfg.log2_cols, pool)
        else:
            self.mesh = geo.site_mesh(self.sites)
            self.state = geo.init_sites(self.mesh, jax.random.key(cfg.seed),
                                        cfg.rows, cfg.log2_cols, pool,
                                        self.sites)
        self._merges = 0
        self._cache: Optional[EmbedCache] = None
        self._pending = 0.0   # mass ingested since the last refresh
        # resilience / health bookkeeping (see health())
        self._lost_mass = 0.0          # estimated mass of dropped shards
        self._lost_shards: tuple = ()  # shard ids lost across updates
        self._update_retries = 0       # retry attempts spent in updates
        # per-shard attempt-latency forensics accumulated across
        # update_shards() calls: shard -> {attempts, failures, buckets}
        # (buckets = counts per resilience.LATENCY_BUCKET_LABELS).
        # Operational telemetry only — deliberately NOT checkpointed.
        self._shard_latency: Dict[int, Dict[str, object]] = {}
        self._refreshes = 0
        self._refresh_failures = 0
        self._last_refresh: Optional[Dict[str, object]] = None

    # ------------------------------------------------------------ ingest
    @property
    def state(self) -> stream_mod.IngestState:
        """The live fold; with several sites, every site's fold on a
        leading (sites,) axis sharded over :attr:`mesh`.  Assigning it
        drops the merged view (:meth:`merge`)."""
        return self._state

    @state.setter
    def state(self, state: stream_mod.IngestState) -> None:
        self._state = state
        self._view = None

    def _site_points(self) -> np.ndarray:
        """Points folded so far, per site (blocks on in-flight folds)."""
        return np.atleast_1d(np.asarray(self.state.count, np.float64))

    def _points(self) -> float:
        if self.sites == 1:
            return float(self.state.count)
        return float(self._site_points().sum())

    def update(self, chunks) -> Dict[str, float]:
        """Fold new data into the live ingest state (no history re-read).

        ``chunks``: a single (n, D) array, an iterable of them, or a
        zero-arg callable factory; with several sites
        (``SnsConfig.sites``), a sequence of one such source per site,
        each folded into its own site's state on its own device
        (``stream.ingest_sites`` feeding ``geo.fold_sites``).  Returns
        absorption stats — points folded, wall seconds (device-synced),
        points/sec — plus the current drift picture
        (``pending_fraction``, ``needs_refresh``).
        """
        with obs.span("sns.update.wait"):
            before = self._points()              # syncs any in-flight fold
        t0 = time.perf_counter()
        if self.sites == 1:
            if pipeline._is_points_array(chunks):
                chunks = [chunks]
            self.state = stream_mod.ingest_all(
                self.state, self.grid, pipeline._chunk_stream(chunks),
                self.cfg.ingest_chunk,
                superbatch=self.cfg.ingest_superbatch)
        else:
            self.state = self._ingest_sites(chunks)
        with obs.span("sns.update.wait"):
            absorbed = self._points() - before   # blocks on the fold
        dt = time.perf_counter() - t0
        self._pending += absorbed
        return {"points": absorbed, "seconds": dt,
                "points_per_sec": absorbed / dt if dt > 0 else 0.0,
                "pending_fraction": self.pending_fraction(),
                "needs_refresh": self.needs_refresh()}

    def _ingest_sites(self, site_chunks) -> stream_mod.IngestState:
        site_chunks = list(site_chunks)
        if len(site_chunks) != self.sites:
            raise ValueError(f"update() of a {self.sites}-site service "
                             f"takes one chunk source per site, got "
                             f"{len(site_chunks)}")
        streams = [pipeline._chunk_stream([c] if pipeline._is_points_array(c)
                                          else c) for c in site_chunks]
        fold = functools.partial(geo.fold_sites, grid=self.grid,
                                 mesh=self.mesh)
        return stream_mod.ingest_sites(
            self.state, streams, self.cfg.ingest_chunk,
            self.cfg.ingest_superbatch, fold,
            NamedSharding(self.mesh, P(geo.SITE_AXIS)))

    def merge(self) -> geo.GeoSketchResult:
        """The master's merged view of every site's fold: the global
        top-K heavy hitters, the merged sketch, the count and the summed
        eviction watermark (``geo.merge_sites``: one SPMD program of
        ``psum``, ``all_gather`` and the top-K).  Kept until the next
        :meth:`update`; :meth:`refresh` and so :meth:`transform` read
        it.  With one site it is that site's own
        fold, and nothing is merged.

        Host span ``sns.geo.merge`` (the dispatch and the blocking read);
        counters ``sns.geo.merges`` and ``sns.geo.merge_bytes`` (the
        table and reservoir bytes every site ships, summed)."""
        if self._view is not None:
            return self._view
        st = self.state
        if self.sites == 1:
            self._view = geo.GeoSketchResult(
                hh=hh_mod.from_candidates(st.sketch, st.cands,
                                          self.cfg.top_k),
                merged=st.sketch, total_count=st.count,
                evict_max=st.evict_max)
            return self._view
        with obs.span("sns.geo.merge"):
            view = jax.block_until_ready(geo.merge_sites(
                st, mesh=self.mesh, top_k=self.cfg.top_k))
        obs.count("sns.geo.merges")
        obs.count("sns.geo.merge_bytes", sum(
            x.nbytes for x in jax.tree_util.tree_leaves(
                (st.sketch.table, st.cands))))
        self._merges += 1
        self._view = view
        return view

    def _check_one_site(self, what: str) -> None:
        if self.sites != 1:
            raise NotImplementedError(
                f"{what} keeps one fold; this service has {self.sites} "
                "sites (update() takes one source per site, merge() "
                "serves their merged view)")

    def update_shards(self, shard_chunks, *,
                      policy: Optional[resilience.RetryPolicy] = None,
                      deadline: Optional[float] = None,
                      min_coverage: float = 0.0,
                      expected_counts=None,
                      faults=None) -> Dict[str, float]:
        """Fold per-shard sources into the live state resiliently.

        ``shard_chunks``: dict ``{shard_id: chunks-or-factory}`` (or a
        sequence, enumerated).  Each shard is ingested independently
        (retried per ``policy``, cut off at ``deadline`` seconds), the
        surviving partial sketches are merged — CountSketch linearity
        makes the merge exactly the fold of the surviving sub-stream —
        and the merged state is folded into the live one.  Lost shards
        widen the served error bound (``health()``) instead of killing
        the service; coverage below ``min_coverage`` raises
        :class:`~repro.core.resilience.CoverageError` WITHOUT touching
        the live fold.
        """
        self._check_one_site("update_shards()")
        if not isinstance(shard_chunks, dict):
            shard_chunks = dict(enumerate(shard_chunks))
        pool = int(self.state.cands.capacity)
        jobs = geo.shard_ingest_jobs(
            self.grid, shard_chunks, seed=self.cfg.seed,
            rows=self.cfg.rows, log2_cols=self.cfg.log2_cols, pool=pool,
            chunk_size=self.cfg.ingest_chunk,
            superbatch=self.cfg.ingest_superbatch, faults=faults)
        t0 = time.perf_counter()
        agg = resilience.collect_shards(
            jobs, policy=policy, deadline=deadline,
            min_coverage=min_coverage, expected_counts=expected_counts,
            verify=True)
        # only now touch the live fold (CoverageError above leaves it be)
        self.state = stream_mod.merge_states(self.state, agg.state)
        absorbed = float(agg.observed_count)
        dt = time.perf_counter() - t0
        self._pending += absorbed
        self._lost_mass += float(agg.lost_mass)
        self._lost_shards = tuple(sorted(set(self._lost_shards)
                                         | set(agg.lost)))
        self._update_retries += agg.retries
        self._fold_shard_latency(agg.statuses)
        return {"points": absorbed, "seconds": dt,
                "points_per_sec": absorbed / dt if dt > 0 else 0.0,
                "coverage": agg.coverage, "lost": list(agg.lost),
                "retries": agg.retries,
                "pending_fraction": self.pending_fraction(),
                "needs_refresh": self.needs_refresh()}

    def _fold_shard_latency(self, statuses) -> None:
        """Accumulate per-shard attempt counts + latency buckets from one
        collector pass into the running histograms (health() exposes
        them).  Buckets are log-spaced per
        ``resilience.LATENCY_BUCKET_LABELS``."""
        nb = len(resilience.LATENCY_BUCKET_LABELS)
        for st in statuses:
            rec = self._shard_latency.setdefault(
                int(st.shard), {"attempts": 0, "failures": 0,
                                "buckets": [0] * nb})
            rec["attempts"] += int(st.attempts)
            rec["failures"] += 0 if st.ok else 1
            hist = resilience.latency_histogram(st.attempt_seconds)
            rec["buckets"] = [a + b for a, b in zip(rec["buckets"], hist)]

    def pending_fraction(self) -> float:
        """Fraction of all ingested mass not yet reflected in the served
        embedding (1.0 before the first refresh)."""
        total = self._points()
        return self._pending / total if total > 0 else 0.0

    def needs_refresh(self) -> bool:
        """Drift / error-bound refresh policy (see ServiceConfig)."""
        if self._cache is None:
            return True
        if self.pending_fraction() >= self.scfg.refresh_drift:
            return True
        return (self.error_bound()
                >= self.scfg.error_ratio * self._cache.min_hh_count)

    def error_bound(self) -> float:
        """Served per-cell count error bound: the space-saving eviction
        watermark widened by the mass of any shards lost in
        :meth:`update_shards` (resilience.widened_bound); with several
        sites, the watermarks summed over the sites, as :meth:`merge`
        sums them (without running a merge)."""
        if self.sites == 1:
            watermark = float(stream_mod.space_saving_bound(self.state))
        else:
            watermark = float(np.asarray(self.state.evict_max,
                                         np.float64).sum())
        return resilience.widened_bound(watermark, self._lost_mass)

    def coverage(self) -> float:
        """Fraction of the offered stream actually folded (1.0 when no
        shard has ever been lost)."""
        seen = self._points()
        offered = seen + self._lost_mass
        return seen / offered if offered > 0 else 1.0

    # ----------------------------------------------------------- refresh
    def refresh(self, mode: str = "auto") -> RefreshResult:
        """Re-extract heavy hitters and re-embed, warm-starting from the
        previous embedding when possible.

        ``mode``: ``"auto"`` (warm iff a previous embedding exists and
        any representative matches), ``"cold"`` (force a from-scratch
        embed), ``"warm"`` (fail loudly if there is nothing to warm from).
        """
        if mode not in ("auto", "cold", "warm"):
            raise ValueError(f"unknown refresh mode: {mode!r}")
        if mode == "warm" and self._cache is None:
            raise ValueError("warm refresh requested but no previous "
                             "embedding exists; run refresh() first")
        t0 = time.perf_counter()
        try:
            cache, result = self._build_snapshot(mode)
        except Exception as e:
            # transactional: the half-built snapshot is dropped on the
            # floor — self._cache still serves the previous embedding
            self._refresh_failures += 1
            self._last_refresh = {
                "ok": False, "mode": mode, "error": repr(e),
                "seconds": time.perf_counter() - t0}
            raise
        # commit: swap the snapshot in atomically (plain attribute
        # assignment — transform() sees either the old or the new cache)
        self._cache = cache
        self._pending = 0.0
        self._refreshes += 1
        self._last_refresh = {
            "ok": True, "mode": mode, "warm": result.warm,
            "n_matched": result.n_matched, "n_new": result.n_new,
            "n_iters": result.n_iters,
            "seconds": time.perf_counter() - t0}
        return result

    def _build_snapshot(self, mode: str):
        """Build the next serving snapshot entirely off to the side.
        Returns (EmbedCache, RefreshResult); never mutates self."""
        cfg = self.cfg
        hh = self.merge().hh
        # same key discipline as pipeline.embed_stage: reps and optimizer
        # draws are bit-reproducible for a given (seed, HH set)
        krep, kembed = jax.random.split(jax.random.key(cfg.seed + 1))
        reps = replicas.make_representatives(
            krep, self.grid, hh, scheme=cfg.replica_scheme,
            max_replicas=cfg.max_replicas, jitter_frac=cfg.jitter_frac)
        pts, w, ids = replicas.compact(reps)
        cells = _packed_cells(hh, ids)
        slots = (np.flatnonzero(np.asarray(reps.mask))
                 % cfg.max_replicas).astype(np.int32)

        init, n_matched, n_new = None, 0, 0
        if mode != "cold" and self._cache is not None:
            init, n_matched, n_new = self._warm_init(pts, cells, slots)
        warm = init is not None
        ecfg, n_iters = self._refresh_ecfg(warm)

        x, wj = jnp.asarray(pts), jnp.asarray(w)
        emb, trace = pipeline.embed_points(cfg, kembed, x, wj, ecfg,
                                           init=init)
        live_counts = np.asarray(hh.count)[np.asarray(hh.mask).astype(bool)]
        cache = EmbedCache(
            rep_cell=cells, rep_slot=slots, rep_x=x, rep_y=emb,
            rep_w=w, rep_ids=ids,
            min_hh_count=float(live_counts.min()) if live_counts.size
            else 0.0)
        result = RefreshResult(embedding=emb, weights=w, hh_ids=ids,
                               warm=warm, n_matched=n_matched,
                               n_new=n_new, n_iters=n_iters,
                               kl_trace=trace)
        return cache, result

    def _warm_init(self, pts, cells, slots):
        """Seed coordinates for the new rep set from the cached embedding:
        returning (cell, slot) identities keep their old position, new
        ones interpolate over their kNN among the matched (inverse square
        distance weights).  Returns (init | None, n_matched, n_new)."""
        cache = self._cache
        prev = {(int(c), int(s)): j for j, (c, s)
                in enumerate(zip(cache.rep_cell, cache.rep_slot))}
        at = np.array([prev.get((int(c), int(s)), -1)
                       for c, s in zip(cells, slots)], np.int64)
        matched = at >= 0
        n_matched = int(matched.sum())
        if n_matched == 0:
            return None, 0, 0
        old_y = np.asarray(cache.rep_y)
        dims = old_y.shape[1]
        y0 = np.zeros((pts.shape[0], dims), np.float32)
        y0[matched] = old_y[at[matched]]
        fresh = ~matched
        n_new = int(fresh.sum())
        if n_new:
            k = min(self.scfg.transform_k, n_matched)
            idx, dist = neighbors.knn_query(
                jnp.asarray(pts[fresh]), jnp.asarray(pts[matched]), k)
            dist = np.asarray(dist)
            wk = 1.0 / (dist * dist + self.scfg.transform_eps)
            wk /= wk.sum(axis=1, keepdims=True)
            y0[fresh] = np.einsum("qk,qkd->qd", wk,
                                  y0[matched][np.asarray(idx)])
        return jnp.asarray(y0), n_matched, n_new

    def _refresh_ecfg(self, warm: bool):
        """Embedder config + iteration count for this refresh.  Warm runs
        skip early exaggeration (the init is already globally arranged —
        exaggeration would tear it apart) and cut iterations ~10×."""
        ecfg = self._ecfg
        if self.cfg.embedder == "tsne":
            cold = ecfg.n_iter
            if not warm:
                return ecfg, cold
            iters = self.scfg.warm_iters or \
                max(1, cold // self.scfg.warm_factor)
            return dataclasses.replace(
                ecfg, n_iter=iters, exaggeration_iters=0,
                momentum_switch=0), iters
        cold = ecfg.n_epochs
        if not warm:
            return ecfg, cold
        iters = self.scfg.warm_iters or \
            max(1, cold // self.scfg.warm_factor)
        return dataclasses.replace(ecfg, n_epochs=iters), iters

    # ------------------------------------------------------------ health
    def health(self) -> Dict[str, object]:
        """One-call serving/ingest health report.

        ``serving`` is True once a refresh has committed a snapshot;
        ``coverage`` / ``lost_shards`` / ``hh_error_bound`` reflect any
        degradation absorbed by :meth:`update_shards`; ``last_refresh``
        records the most recent refresh outcome (including failures the
        transactional swap rolled back); ``ingest`` holds the
        process-wide host spans and counters of the ingest path
        (``repro.obs.snapshot()``: ``sns.ingest.*``, ``sns.update.wait``,
        ``sns.geo.*``); ``geo`` the sites, the devices they are spread
        over, each site's points and the merges run."""
        c = self._cache
        return {
            "serving": c is not None,
            "n_reps": int(c.rep_y.shape[0]) if c is not None else 0,
            "points": self._points(),
            "pending_fraction": self.pending_fraction(),
            "needs_refresh": self.needs_refresh(),
            "hh_error_bound": self.error_bound(),
            "coverage": self.coverage(),
            "lost_shards": self._lost_shards,
            "update_retries": self._update_retries,
            # per-shard latency forensics: attempt counts + log-spaced
            # per-attempt wall-clock buckets (resilience.
            # LATENCY_BUCKET_LABELS), accumulated over update_shards()
            "shard_latency": {
                s: {"attempts": rec["attempts"],
                    "failures": rec["failures"],
                    "buckets": dict(zip(resilience.LATENCY_BUCKET_LABELS,
                                        rec["buckets"]))}
                for s, rec in sorted(self._shard_latency.items())},
            "refreshes": self._refreshes,
            "refresh_failures": self._refresh_failures,
            "last_refresh": self._last_refresh,
            "ingest": obs.snapshot(),
            "geo": {"sites": self.sites,
                    "devices": 1 if self.mesh is None
                    else int(self.mesh.devices.size),
                    "site_points": self._site_points().tolist(),
                    "merges": self._merges},
        }

    # --------------------------------------------------------- transform
    def transform(self, queries) -> np.ndarray:
        """Embed raw query points against the frozen served embedding —
        no optimizer.  (Q, D) → (Q, dims); one jitted chunked pass, peak
        memory O(transform_chunk · N_reps)."""
        if self._cache is None:
            raise ServiceNotReadyError(
                "transform() needs a served embedding; call "
                "refresh() first")
        q = np.asarray(queries, np.float32)
        squeeze = q.ndim == 1
        if squeeze:
            q = q[None, :]
        n = q.shape[0]
        if n == 0:
            return np.zeros((0, self._cache.rep_y.shape[1]), np.float32)
        chunk = max(1, min(self.scfg.transform_chunk, n))
        k = min(self.scfg.transform_k, int(self._cache.rep_x.shape[0]))
        pad = (-n) % chunk
        if pad:
            q = np.concatenate(
                [q, np.zeros((pad, q.shape[1]), np.float32)])
        y = _transform_chunks(jnp.asarray(q), self._cache.rep_x,
                              self._cache.rep_y, k, chunk,
                              self.scfg.transform_eps)
        out = np.asarray(y[:n])
        return out[0] if squeeze else out

    # ------------------------------------------------------- persistence
    def save(self, path) -> None:
        """Checkpoint the live fold AND the serving snapshot to one
        ``.npz`` (via ``stream.save_state`` extras).  The write is atomic
        and checksummed, and the previous checkpoint generation rotates
        to ``<path>.npz.bak`` — :meth:`load` falls back to it if this
        write is later found torn or corrupted."""
        self._check_one_site("save()")
        if self._cache is None:
            raise ServiceNotReadyError(
                "save() checkpoints the serving snapshot; call refresh() "
                "first (to checkpoint a fold alone, use stream.save_state "
                "on .state)")
        extra = {"pending": np.float64(self._pending),
                 "lost_mass": np.float64(self._lost_mass),
                 "lost_shards": np.asarray(self._lost_shards, np.int64),
                 "update_retries": np.int64(self._update_retries)}
        c = self._cache
        extra.update(
            rep_cell=c.rep_cell, rep_slot=c.rep_slot,
            rep_x=np.asarray(c.rep_x), rep_y=np.asarray(c.rep_y),
            rep_w=c.rep_w, rep_ids=c.rep_ids,
            min_hh_count=np.float64(c.min_hh_count))
        stream_mod.save_state(self.state, path, extra=extra,
                              keep_backup=True)

    @classmethod
    def load(cls, path, cfg: SnsConfig, grid: GridSpec, *,
             tsne_cfg=None, umap_cfg=None,
             service_cfg: Optional[ServiceConfig] = None) -> "SnsService":
        """Resurrect a service from :meth:`save` — the fold continues and
        the served embedding (if one was cached) serves immediately.
        Checksums are verified; if the newest checkpoint is corrupt the
        ``.bak`` generation (rotated by :meth:`save`) is loaded instead
        (``stream.load_state(fallback=True)``)."""
        svc = cls(cfg, grid, tsne_cfg=tsne_cfg, umap_cfg=umap_cfg,
                  service_cfg=service_cfg)
        svc._check_one_site("load()")
        state, extras = stream_mod.load_state(path, with_extra=True,
                                              fallback=True)
        svc.state = state
        svc._pending = float(extras.get("pending", 0.0))
        svc._lost_mass = float(extras.get("lost_mass", 0.0))
        svc._lost_shards = tuple(
            int(s) for s in extras.get("lost_shards", ()))
        svc._update_retries = int(extras.get("update_retries", 0))
        if "rep_y" in extras:
            svc._cache = EmbedCache(
                rep_cell=extras["rep_cell"].astype(np.uint64),
                rep_slot=extras["rep_slot"].astype(np.int32),
                rep_x=jnp.asarray(extras["rep_x"]),
                rep_y=jnp.asarray(extras["rep_y"]),
                rep_w=extras["rep_w"],
                rep_ids=extras["rep_ids"],
                min_hh_count=float(extras["min_hh_count"]))
        return svc
