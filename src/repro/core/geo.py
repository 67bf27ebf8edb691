"""Geo-distributed sketching: the paper's topology on a JAX device mesh.

Paper §V: data at different geographic locations is sketched *in place*;
only the fixed-size sketches move; aggregation is a tree — within one data
center first, across data centers second.  On a TPU mesh that hierarchy is
exactly (ICI within a pod) × (DCN across pods):

    mesh axes ("pod", "data"):  "data" = workers inside one data center,
                                "pod"  = data centers.

One geo path, two programs.  The sites' folds live in one
``stream.IngestState`` whose leaves have a leading (sites,) axis sharded
over the mesh: :func:`fold_sites` (``stream.ingest_step`` in a
``lax.scan`` under ``shard_map``, no collective) folds every site's own
points in place, and :func:`merge_sites` is the master's merge —
``psum`` of the sketch tables, counts and eviction watermarks over
"data" then "pod" (linearity!), ``all_gather`` of the reservoirs, and
the global top-K estimated on the merged sketch, replicated on every
device.  ``SnsService`` (``SnsConfig.sites``) runs them on the 1-D
``"site"`` mesh of :func:`site_mesh`; ``geo_extract_from_shards`` folds
points made inside the program per site, and the one-shot
``geo_extract`` (``sketch_shard``: local top-L, no reservoir) ends in
the same merge.

Privacy note (paper §V): only hashed, signed *sums* ever cross the pod
axis — the sketch is non-invertible; raw coordinates never leave a shard.

This module is also the template for the LM-side activation sketcher
(``repro.train.callbacks``) which reuses ``sketch_shard`` verbatim on
hidden-state projections.

The mesh/axis plumbing this stage pioneered (the ``shard_map`` compat
shim, linear shard indexing, row-block sizing) now lives in
:mod:`repro.core.mesh`, shared with the mesh-parallel EMBED stage
(``core.tsne``/``core.umap`` row-block-shard their iteration loops the
same way; ``SnsConfig.embed_mesh`` wires it through the pipeline) — the
whole ingest → HH → embed chain can run without leaving ``shard_map``.

Failure semantics (two tiers, by construction):

* The SPMD paths above (``geo_extract``, ``geo_extract_from_shards``,
  the sites of ``SnsService``) run as ``shard_map`` programs — XLA
  collectives cannot lose a
  participant, so a dead device fails the whole dispatch.  Nothing is
  retried and nothing degrades; this tier is all-or-nothing by design.
* :func:`resilient_extract` is the HOST-level topology the paper
  actually describes (edge nodes ship summaries to a master): each
  shard's fold is an independent job, transient failures are RETRIED
  under a ``resilience.RetryPolicy``, stragglers are cut off at a
  deadline, and lost shards DEGRADE into partial aggregation — the
  surviving sketches merge linearly (``stream.merge_states``), coverage
  drops below 1, and the heavy-hitter error bound widens by the
  estimated lost mass.  ``min_coverage`` is the fail-loud floor.
"""
from __future__ import annotations

import functools
from typing import (Callable, Dict, List, Mapping, NamedTuple, Optional,
                    Sequence, Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import candidates as cand_mod
from repro.core import heavy_hitters as hh_mod
from repro.core import mesh as mesh_mod
from repro.core import quantize, sketch as sketch_mod
from repro.core import stream as stream_mod
from repro.core.candidates import Candidates
from repro.core.heavy_hitters import HeavyHitters
from repro.core.mesh import shard_map_compat  # noqa: F401 (hoisted; re-export)
from repro.core.quantize import GridSpec
from repro.core.sketch import CountSketch


class GeoSketchResult(NamedTuple):
    hh: HeavyHitters            # replicated global top-K
    merged: CountSketch         # replicated merged sketch
    total_count: jnp.ndarray    # psum'd global item count (stream mass)
    # psum'd candidate-stage watermark: each site's largest count ever
    # withheld from its candidate set (local top-L truncation in the
    # one-shot path, reservoir eviction in the streaming path), summed
    # over the sites — a cell no site proposed had at most its site's
    # watermark at each site, so at most the sum in all; 0 ⇒ every
    # occupied cell was proposed — the HH candidate set is complete
    evict_max: jnp.ndarray


SITE_AXIS = "site"     # the 1-D mesh axis of SnsService's sites


def site_mesh(sites: int) -> Mesh:
    """The 1-D ``"site"`` mesh over ``jax.devices()[:min(sites, n)]``:
    one site per device where there are enough, else ``sites / n`` sites
    on each (``sites`` must divide evenly across the devices)."""
    devs = jax.devices()[:min(sites, len(jax.devices()))]
    if sites % len(devs):
        raise ValueError(f"{sites} sites do not divide evenly across "
                         f"{len(devs)} devices")
    return Mesh(np.asarray(devs), (SITE_AXIS,))


def init_sites(mesh: Mesh, key: jax.Array, rows: int, log2_cols: int,
               pool: int, sites: int) -> stream_mod.IngestState:
    """``sites`` fresh folds as one state whose leaves have a leading
    (sites,) axis, sharded over the mesh's axes.  Every site draws its
    hash parameters from the same ``key`` (the paper's shared-hash
    rule), so the sites' sketches merge by addition."""
    st = stream_mod.init(key, rows, log2_cols, pool)
    st = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (sites,) + x.shape), st)
    return jax.device_put(st, NamedSharding(mesh, P(mesh.axis_names)))


def _each_site(fold, *args):
    """``fold`` over the leading (local sites,) axis of ``args`` inside a
    ``shard_map`` body: one site on this device is folded as it is (the
    one-site program), several one after another (``lax.map``)."""
    if jax.tree_util.tree_leaves(args[0])[0].shape[0] == 1:
        out = fold(*jax.tree_util.tree_map(lambda x: x[0], args))
        return jax.tree_util.tree_map(lambda x: x[None], out)
    return jax.lax.map(lambda a: fold(*a), args)


@functools.partial(jax.jit, static_argnames=("grid", "mesh"),
                   donate_argnums=(0,))
def fold_sites(state: stream_mod.IngestState, points: jnp.ndarray,
               mask: jnp.ndarray, *, grid: GridSpec, mesh: Mesh
               ) -> stream_mod.IngestState:
    """Every site folds its own (B, chunk, D) superbatch of the
    (sites, B, chunk, D) ``points`` into its own state, in one dispatch:
    ``stream.fold_superbatch`` (``ingest_step`` in a ``lax.scan``) under
    ``shard_map`` over the mesh's axes, the state donated.  No
    collective: sites never talk while they fold."""
    spec = P(mesh.axis_names)

    @shard_map_compat(mesh=mesh, in_specs=(spec, spec, spec),
                      out_specs=spec)
    def spmd(st, pts, m):
        return _each_site(
            lambda s, p, mk: stream_mod.fold_superbatch(s, grid, p, mk),
            st, pts, m)

    return spmd(state, points, mask)


def _merge_local(st: stream_mod.IngestState, top_k: int,
                 axes: Tuple[str, ...]) -> GeoSketchResult:
    """The master's merge, inside a ``shard_map`` body whose ``st`` holds
    this device's sites on a leading axis: ``psum`` of the tables, the
    counts and the watermarks (innermost axis first), ``all_gather`` of
    the reservoirs, then the top ``top_k`` of the gathered keys estimated
    on the merged sketch.  Only these summaries cross devices."""
    with jax.named_scope("sns.geo.psum"):
        sums = (jnp.sum(st.sketch.table, axis=0), jnp.sum(st.count),
                jnp.sum(st.evict_max))
        for ax in axes:
            sums = jax.lax.psum(sums, ax)
        table, total, evict = sums
    with jax.named_scope("sns.geo.gather"):
        local = jax.tree_util.tree_map(
            lambda x: x.reshape((-1,) + x.shape[2:]), st.cands)
        gathered = cand_mod.all_gather(local, axes)
    with jax.named_scope("sns.geo.extract"):
        merged = CountSketch(table=table, params=jax.tree_util.tree_map(
            lambda p: p[0], st.sketch.params))
        hh = hh_mod.from_candidates(merged, gathered, top_k)
    return GeoSketchResult(hh=hh, merged=merged, total_count=total,
                           evict_max=evict)


def merge_shard(sk_local: CountSketch, cands: Candidates,
                count: jnp.ndarray, evict_max: jnp.ndarray, top_k: int,
                axes: Tuple[str, ...]) -> GeoSketchResult:
    """:func:`_merge_local` of the one site a device holds, given as its
    sketch, candidates, count and watermark (inside ``shard_map``)."""
    st = stream_mod.IngestState(sketch=sk_local, cands=cands, count=count,
                                evict_max=evict_max)
    return _merge_local(jax.tree_util.tree_map(lambda x: x[None], st),
                        top_k, axes)


@functools.partial(jax.jit, static_argnames=("mesh", "top_k", "axes"))
def merge_sites(state: stream_mod.IngestState, *, mesh: Mesh, top_k: int,
                axes: Optional[Tuple[str, ...]] = None) -> GeoSketchResult:
    """One SPMD program: the sites' folds (leading axis sharded over
    ``axes``, all the mesh's by default) merged into the replicated
    global view (:func:`_merge_local`)."""
    axes = tuple(axes or mesh.axis_names)

    @shard_map_compat(mesh=mesh, in_specs=(P(axes),), out_specs=P())
    def spmd(st):
        return _merge_local(st, top_k, axes)

    return spmd(state)


def sketch_shard(sk: CountSketch, grid: GridSpec, points: jnp.ndarray,
                 candidate_pool: int,
                 mask: Optional[jnp.ndarray] = None,
                 ) -> Tuple[CountSketch, Candidates, jnp.ndarray]:
    """One edge node's work: quantize → pack → ONE sort+RLE feeding both
    the sketch scatter and the local top-L (the fused single-sort layout;
    the pre-fusion path sorted the same keys twice).  Also returns the
    local truncation watermark (largest count NOT proposed; 0 = none)."""
    key_hi, key_lo = quantize.points_to_keys(grid, points)
    runs = cand_mod.sorted_runs(
        key_hi, key_lo, mask=mask,
        assume_hi_zero=grid.dims * grid.bits_per_dim <= 32)
    sk = sketch_mod.update_runs(sk, runs)
    cands, dropped = cand_mod.topk_from_runs(runs, candidate_pool,
                                             return_dropped=True)
    return sk, cands, dropped


def geo_extract(mesh: Mesh, grid: GridSpec, points: jnp.ndarray,
                *, rows: int, log2_cols: int, top_k: int,
                candidate_pool: int = 0,
                data_axes: Union[str, Sequence[str]] = ("data",),
                seed: int = 0) -> GeoSketchResult:
    """End-to-end distributed heavy-hitter extraction.

    ``points``: (N, D) global array, batch dim sharded over ``data_axes``.
    Runs as a single SPMD program: every device sketches its shard, the
    sketches psum-merge hierarchically, candidates all_gather, and the
    replicated global top-K comes back.
    """
    if isinstance(data_axes, str):
        data_axes = (data_axes,)
    pool = candidate_pool or 2 * top_k
    # Hash params are drawn OUTSIDE shard_map from a shared seed — the
    # paper's requirement that every site uses identical hash functions.
    sk0 = sketch_mod.init(jax.random.key(seed), rows, log2_cols)

    pspec = P(tuple(data_axes))

    @shard_map_compat(mesh=mesh, in_specs=(P(), pspec), out_specs=P())
    def spmd(sk, pts):
        sk_local, cands, dropped = sketch_shard(sk, grid, pts, pool)
        return merge_shard(sk_local, cands,
                           jnp.full((), pts.shape[0], jnp.float32),
                           dropped, top_k, tuple(data_axes))

    return spmd(sk0, points)


def geo_extract_from_shards(mesh: Mesh, grid: GridSpec,
                            shard_fn, *, rows: int, log2_cols: int,
                            top_k: int, candidate_pool: int = 0,
                            data_axes: Union[str, Sequence[str]] = ("data",),
                            seed: int = 0, num_batches: int = 1
                            ) -> GeoSketchResult:
    """Streaming variant: each device *generates/loads* its own batches via
    ``shard_fn(device_linear_index, batch_index) -> (points, mask)`` traced
    inside the SPMD program (e.g. a synthetic generator or a sharded file
    reader).  ``batch_index`` arrives as a traced int32 scalar — index data
    with ``lax.dynamic_slice``/gather or fold it into a PRNG key.

    The batch loop is a ``lax.scan`` carrying ``stream.IngestState``
    (sketch ⊕ bounded candidate reservoir ⊕ count ⊕ eviction watermark),
    so per-device memory is O(batch + candidate_pool + sketch) regardless
    of stream length, and the trace is O(1) in ``num_batches`` — the
    paper's 'single stream I/O' regime.  (The previous implementation
    retained every batch's keys and Python-unrolled the loop, making both
    memory and trace O(stream); tests/test_stream_ingest.py pins the fixed
    behaviour via the jaxpr.)  The step is the fused single-sort fold
    (``stream.ingest_step``): one sort per batch feeds both the sketch
    scatter and the sorted-merge reservoir update —
    tests/test_fused_ingest.py pins the one-sort-per-step jaxpr.  Each
    device's fold leaves the program as its site's slice of a sharded
    state, and :func:`merge_sites` merges the sites as it does for
    ``SnsService``.
    """
    if isinstance(data_axes, str):
        data_axes = (data_axes,)
    data_axes = tuple(data_axes)
    pool = candidate_pool or 2 * top_k
    sk0 = sketch_mod.init(jax.random.key(seed), rows, log2_cols)

    @jax.jit
    @shard_map_compat(mesh=mesh, in_specs=(P(),), out_specs=P(data_axes))
    def fold(sk):
        idx = mesh_mod.linear_index(mesh, data_axes)

        def step(st, b):
            pts, mask = shard_fn(idx, b)
            return stream_mod.ingest_step(st, grid, pts, mask=mask), ()

        st0 = stream_mod.from_sketch(sk, pool)
        st, _ = jax.lax.scan(step, st0,
                             jnp.arange(num_batches, dtype=jnp.int32))
        return jax.tree_util.tree_map(lambda x: x[None], st)

    return merge_sites(fold(sk0), mesh=mesh, top_k=top_k, axes=data_axes)


class ResilientExtractResult(NamedTuple):
    """Partial-aggregation-aware extraction result: the usual replicated
    outputs plus the quantified damage of whatever was lost."""
    hh: HeavyHitters              # top-K over the OBSERVED sub-stream
    merged: CountSketch           # merge of the shards that delivered
    observed_count: float         # mass actually folded
    coverage: float               # observed / expected   (1.0 = no loss)
    hh_error_bound: float         # survivor watermark + estimated lost mass
    lost: Tuple[int, ...]         # shard ids that never delivered
    statuses: list                # per-shard resilience.ShardStatus
    retries: int                  # extra attempts beyond the first, total


def shard_ingest_jobs(grid: GridSpec, shard_chunks: Mapping, *,
                      seed: int, rows: int, log2_cols: int, pool: int,
                      chunk_size: int, superbatch: int = 1,
                      faults=None) -> Dict[int, Callable[[], tuple]]:
    """Build the per-shard fold jobs ``resilience.collect_shards`` runs.

    ``shard_chunks`` maps shard id → a chunk source (an iterable of
    (n, D) arrays, or a zero-arg callable returning one — callables are
    re-invoked per attempt, so a retried shard re-reads its data).  All
    jobs draw hash params from the SAME seed (the paper's identical-
    hash-functions contract), so their states merge linearly.  Each job
    returns ``(state, digest)`` with the digest computed at the source —
    the collector's ``verify=True`` detects in-transit corruption.

    ``faults`` (a :class:`repro.core.faults.FaultPlan`) wraps both the
    chunk stream and the job itself — the reproducible-chaos hook the
    tests and ``bench_resilience`` drive."""
    import dataclasses as _dc

    from repro.core import faults as faults_mod

    # split the plan across its two injection points: delivery faults
    # (drop / flaky / delay) fire ONCE per attempt in chaos_shard_job —
    # whose counter ticks on every attempt — while the chunk wrapper
    # inside the job carries only the data faults (duplicate / corrupt).
    # Injecting delivery faults at both levels would double-apply them
    # with drifting attempt counters (the inner one only advances when
    # the outer roll passes), turning transient faults semi-permanent.
    chunk_faults = None if faults is None else _dc.replace(
        faults, drop=0.0, drop_shards=(), flaky=0.0, delay=0.0)

    jobs: Dict[int, Callable[[], tuple]] = {}
    for shard, source in shard_chunks.items():
        def job(shard=shard, source=source, attempt_box=[0]):
            attempt = attempt_box[0]
            attempt_box[0] += 1
            chunks = source() if callable(source) else source
            if chunk_faults is not None:
                chunks = faults_mod.chaos_chunks(chunk_faults, shard,
                                                 chunks, attempt=attempt)
            st = stream_mod.init(jax.random.key(seed), rows, log2_cols,
                                 pool)
            st = stream_mod.ingest_all(st, grid, chunks, chunk_size,
                                       superbatch=superbatch)
            st = jax.tree_util.tree_map(
                lambda x: jax.device_get(x), st)   # ship host-side bytes
            return st, stream_mod.state_digest(st)
        if faults is not None:
            # job-level faults (permanent drop / flaky / delay / state
            # corruption after the digest) stack on the chunk-level ones
            jobs[shard] = faults_mod.chaos_shard_job(faults, shard, job)
        else:
            jobs[shard] = job
    return jobs


def resilient_extract(grid: GridSpec, shard_chunks, *,
                      rows: int, log2_cols: int, top_k: int,
                      candidate_pool: int = 0, seed: int = 0,
                      chunk_size: int = 65_536, superbatch: int = 1,
                      policy=None, deadline: Optional[float] = None,
                      min_coverage: float = 0.0,
                      expected_counts: Optional[Mapping[int, float]] = None,
                      faults=None) -> ResilientExtractResult:
    """Host-level fault-tolerant heavy-hitter extraction.

    The paper's actual topology: every shard folds its own stream into a
    (sketch ⊕ reservoir) summary and ships ONLY the summary; the master
    merges.  Unlike the ``shard_map`` SPMD paths, shards here are
    independent host-side jobs, so the full failure menu applies and is
    handled (see the module docstring's failure-semantics contract):
    transient errors retry under ``policy``, stragglers are cut off at
    ``deadline``, permanent losses degrade into partial aggregation with
    ``coverage < 1`` and a widened ``hh_error_bound``, and coverage
    below ``min_coverage`` raises ``resilience.CoverageError``.

    ``shard_chunks``: mapping shard id → chunk source, or a sequence
    (ids 0..S-1).  ``grid`` must be agreed up front (the shared-
    hypercube contract — geo-distributed sites cannot take a global
    min/max pass)."""
    from repro.core import resilience

    if not isinstance(shard_chunks, Mapping):
        shard_chunks = dict(enumerate(shard_chunks))
    if not shard_chunks:
        raise ValueError("resilient_extract needs at least one shard")
    pool = candidate_pool or 2 * top_k
    jobs = shard_ingest_jobs(
        grid, shard_chunks, seed=seed, rows=rows, log2_cols=log2_cols,
        pool=pool, chunk_size=chunk_size, superbatch=superbatch,
        faults=faults)
    agg = resilience.collect_shards(
        jobs, policy=policy, deadline=deadline, min_coverage=min_coverage,
        expected_counts=expected_counts, verify=True)
    hh = hh_mod.from_candidates(agg.state.sketch, agg.state.cands, top_k)
    return ResilientExtractResult(
        hh=hh, merged=agg.state.sketch,
        observed_count=agg.observed_count, coverage=agg.coverage,
        hh_error_bound=agg.hh_error_bound, lost=agg.lost,
        statuses=agg.statuses, retries=agg.retries)
