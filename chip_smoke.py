#!/usr/bin/env python3
"""Run the SnS main path once on a TPU and check what comes out.

    python chip_smoke.py              # phases A and B on one chip
    python chip_smoke.py --chips 4    # four sites on four chips vs one chip

Phase A — the cancer survey as a service (``configs.sns_paper.CANCER``:
8-D, 25 bins, a 16 x 2^18 sketch, top 20,000 heavy hitters, count
replicas, UMAP 2-D).  An ``SnsService`` takes in 26,000,000 seeded 8-D
points through ``update()`` in chunks, runs one ``refresh()`` and answers
three ``transform()`` batches of 4,096 queries.  Checks: the ingested
count equals the points fed; the served heavy hitters agree with an
exact host-side count of the same stream (below); the embedding and the
transformed points are finite, and every transformed point lies inside
the embedding's bounding box (the placement is a convex combination of
embedded representatives).

Phase B — the million-representative embed (``configs.sns_paper.
CANCER_1M``: sparse tSNE, approximate kNN with k = 3 x perplexity, the
adaptive grid starting at G = 256 and capped at 1024) through
``pipeline.embed_points`` on 10^6 seeded 8-D representatives for a few
tens of iterations, at the large-N learning rate N / 12.  Checks: the
embedding is finite and the KL trace is finite and goes down.

``--chips 4`` runs only the four-chip comparison: ``pipeline.
run_streaming(mesh=..., shard_fn=...)`` with one site per chip and the
embed stage on a 4-device embed mesh (one UMAP epoch), against the same
four site streams folded one after another on one chip and the same
representatives embedded on one device.  Checks: the heavy-hitter set
(keys, counts, mask) is bit-identical, and the 4-chip embedding matches
the single-device one within the epoch-1 tolerance of
tests/test_mesh_embed.py (1e-4 of the embedding scale).

The heavy-hitter check of phase A.  Served counts are Count Sketch
estimates, so they carry the sketch error as well as the candidate
stage's bound ``hh_error_bound`` (the largest count the reservoir ever
evicted).  With ``err`` the largest |estimate - exact| over the served
cells, the check requires ``err <= 5 sqrt(F2 / C)`` (F2 the exact second
moment of the cell counts, C the sketch width: the Count Sketch error
scale) and that no cell of the exact top-K left out of the served set
has an exact count above ``max(min served exact count + 2 err,
hh_error_bound)``: the served set is the exact top-K up to those bounds.

Every phase prints its sizes (and any cut), the registry tier each
kernel op resolves to, and its compile and run seconds apart (compile =
lowering + backend compile time, from ``jax.monitoring``).
The last line of standard output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The script exits non-zero, without that line, when JAX finds no TPU,
when the repository's ``src/`` is not next to it, or when any check
fails.  It touches JAX in this one process only.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

PHASE_A_POINTS = 26_000_000     # paper §IV: 52M pixels, 26M after noise cut
PHASE_A_CHUNK = 1 << 20         # points per update() call
QUERY_BATCH = 4096
N_QUERY_BATCHES = 3
PHASE_B_REPS = 1_000_000
PHASE_B_ITERS = 40
PHASE_B_STAGE = 10              # iterations between adaptive-grid checks
FOUR_CHIP_SITES = 4
FOUR_CHIP_BATCHES = 100         # 65,536-point batches per site
UMAP_EPOCH1_TOL = 1e-4          # tests/test_mesh_embed.py, epoch-1 prefix
SKETCH_ERR_SIGMAS = 5.0


class CheckFailed(RuntimeError):
    """A phase produced a wrong result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileMeter:
    """Seconds JAX spends lowering and compiling, summed from
    ``jax.monitoring`` duration events, so that a phase's wall time splits
    into compile and run.  Tracing is left in the run time: nested jits
    trace inside their caller, so trace events would count twice."""
    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax_mod):
        self.seconds = 0.0
        self.compiles = 0
        jax_mod.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
            self.compiles += event == self.EVENTS[-1]

    def span(self):
        return _Span(self)


class _Span:
    def __init__(self, meter):
        self.meter = meter

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.meter.seconds
        self.n0 = self.meter.compiles
        return self

    def __exit__(self, *exc):
        self.wall = time.perf_counter() - self.t0
        self.compile = self.meter.seconds - self.c0
        self.n_compiles = self.meter.compiles - self.n0
        self.run = max(self.wall - self.compile, 0.0)

    def line(self) -> str:
        return (f"wall {self.wall:.3f} s = compile {self.compile:.3f} s "
                f"({self.n_compiles} compiles) + run {self.run:.3f} s")


def tiers(jax_mod) -> dict:
    """The registry tier every kernel op resolves to under auto mode on
    this backend."""
    from repro.kernels import registry
    backend = jax_mod.default_backend()
    return {op: registry.resolve(op, backend=backend).mode
            for op in registry.list_ops()}


def on_path(cfg) -> list:
    """Registry ops the configuration's embed stage calls."""
    ops = ["segment_reduce"]
    if cfg.embed_cic == "pallas":
        ops += ["cic_splat", "cic_gather"]
    if cfg.embed_backend == "pallas":
        ops.append("tsne_step")
    ann = cfg.embed_ann
    if ann is not None and ann.tile == "pallas":
        ops.append("knn_dist_tiles")
    return ops


# ---------------------------------------------------------------- phase A
def host_keys(points: np.ndarray, lo: np.ndarray, hi: np.ndarray,
              bins: int) -> np.ndarray:
    """Exact reference quantizer in NumPy: float32 floor((p - lo) * M /
    (hi - lo)) clipped to [0, M), bit-packed most significant dim first,
    ceil(log2 M) bits per dim."""
    inv = (np.float32(bins) / (hi - lo)).astype(np.float32)
    q = np.floor((points - lo) * inv)
    q = np.clip(q, 0, bins - 1).astype(np.uint64)
    bits = np.uint64(max(1, math.ceil(math.log2(bins))))
    key = np.zeros(points.shape[0], np.uint64)
    for i in range(points.shape[1]):
        key = (key << bits) | q[:, i]
    return key


def phase_a(jax, meter, n_points: int, seed: int) -> None:
    from repro.configs import sns_paper
    from repro.core import heavy_hitters as hh_mod, quantize
    from repro.core.service import SnsService
    from repro.data.synthetic import MixtureSpec, clustered_points_sharded

    cfg = dataclasses.replace(sns_paper.CANCER, seed=seed)
    spec = MixtureSpec(dims=8)
    log(f"[A] config CANCER: dims 8, bins {cfg.bins}, sketch {cfg.rows}x"
        f"2^{cfg.log2_cols}, top_k {cfg.top_k}, replicas "
        f"{cfg.replica_scheme} (max {cfg.max_replicas}), {cfg.embedder} "
        f"{cfg.embed_dims}-D")
    cut = "" if n_points == PHASE_A_POINTS else \
        f" (CUT from {PHASE_A_POINTS:,})"
    log(f"[A] sizes: {n_points:,} points{cut} in update() calls of "
        f"{PHASE_A_CHUNK:,}; {N_QUERY_BATCHES} transform batches of "
        f"{QUERY_BATCH:,}")
    log(f"[A] tiers (auto): {json.dumps(tiers(jax))}; on path: "
        f"{on_path(cfg)}")

    t0 = time.perf_counter()
    chunks, keys = [], []
    lo = np.zeros(8, np.float32)
    hi = np.ones(8, np.float32)
    for i, start in enumerate(range(0, n_points, PHASE_A_CHUNK)):
        n = min(PHASE_A_CHUNK, n_points - start)
        c = clustered_points_sharded(i, n, spec, seed=seed)
        chunks.append(c)
        keys.append(host_keys(c, lo, hi, cfg.bins))
    keys = np.concatenate(keys)
    uniq, exact = np.unique(keys, return_counts=True)
    log(f"[A] set-up: data + exact host count {time.perf_counter() - t0:.3f}"
        f" s ({uniq.size:,} distinct cells)")

    grid = quantize.GridSpec(dims=8, bins=cfg.bins, lo=tuple(lo),
                             hi=tuple(hi))
    svc = SnsService(cfg, grid)
    with meter.span() as s:
        for c in chunks:
            svc.update(c)
        fed = float(svc.health()["points"])
    log(f"[A] update: {n_points:,} points, {s.line()}")
    check(fed == float(n_points),
          f"ingested count {fed} != points fed {n_points}")

    with meter.span() as s:
        res = svc.refresh()
        emb = np.asarray(res.embedding)
    log(f"[A] refresh: {emb.shape[0]:,} representatives -> {emb.shape}, "
        f"{res.n_iters} epochs, {s.line()}")
    check(emb.ndim == 2 and emb.shape[1] == cfg.embed_dims
          and emb.shape[0] > 0, f"embedding shape {emb.shape}")
    check(bool(np.isfinite(emb).all()), "embedding has non-finite values")

    rng = np.random.default_rng(seed + 7)
    box_lo, box_hi = emb.min(axis=0), emb.max(axis=0)
    span = float(np.max(box_hi - box_lo))
    for b in range(N_QUERY_BATCHES):
        q = clustered_points_sharded(10_000 + b, QUERY_BATCH, spec,
                                     seed=int(rng.integers(1 << 30)))
        with meter.span() as s:
            y = svc.transform(q)
        log(f"[A] transform batch {b}: {q.shape} -> {y.shape}, {s.line()}")
        check(y.shape == (QUERY_BATCH, cfg.embed_dims),
              f"transform shape {y.shape}")
        check(bool(np.isfinite(y).all()), "transform has non-finite values")
        tol = 1e-5 * max(span, 1.0)
        check(bool(((y >= box_lo - tol) & (y <= box_hi + tol)).all()),
              "transformed point outside the embedding's bounding box")

    # served heavy hitters vs the exact count of the same stream
    hh = hh_mod.from_candidates(svc.state.sketch, svc.state.cands,
                                cfg.top_k)
    live = np.asarray(hh.mask).astype(bool)
    hkeys = (np.asarray(hh.key_hi, np.uint64)[live] << np.uint64(32)) \
        | np.asarray(hh.key_lo, np.uint64)[live]
    est = np.asarray(hh.count, np.float64)[live]
    bound = float(svc.health()["hh_error_bound"])
    check(hkeys.size == min(cfg.top_k, uniq.size),
          f"{hkeys.size} served heavy hitters, want "
          f"{min(cfg.top_k, uniq.size)}")
    check(np.unique(hkeys).size == hkeys.size, "duplicate served cells")
    pos = np.searchsorted(uniq, hkeys)
    found = (pos < uniq.size) & (uniq[np.minimum(pos, uniq.size - 1)]
                                 == hkeys)
    check(bool(found.all()), "a served cell never occurs in the stream")
    f_served = exact[pos].astype(np.float64)
    err = float(np.max(np.abs(est - f_served)))
    sigma = math.sqrt(float(np.sum(exact.astype(np.float64) ** 2))
                      / (1 << cfg.log2_cols))
    order = np.argsort(-exact, kind="stable")[:hkeys.size]
    missed = ~np.isin(uniq[order], hkeys)
    max_missed = float(exact[order][missed].max()) if missed.any() else 0.0
    min_served = float(f_served.min())
    allowed = max(min_served + 2.0 * err, bound)
    log(f"[A] heavy hitters vs exact count: served {hkeys.size:,}, "
        f"sketch err max {err:.1f} (sigma {sigma:.1f}), hh_error_bound "
        f"{bound:.1f}, exact top-K missed {int(missed.sum())} (max exact "
        f"count {max_missed:.0f} <= allowed {allowed:.1f}), min served "
        f"exact count {min_served:.0f}, coverage {svc.coverage():.6f}")
    check(err <= SKETCH_ERR_SIGMAS * sigma,
          f"sketch error {err} > {SKETCH_ERR_SIGMAS} sigma ({sigma})")
    check(max_missed <= allowed,
          f"exact top-K cell of count {max_missed} left out (allowed "
          f"{allowed})")


# ---------------------------------------------------------------- phase B
def phase_b(jax, meter, n_reps: int, n_iter: int, seed: int) -> None:
    import jax.numpy as jnp

    from repro.configs import sns_paper
    from repro.core import pipeline, tsne
    from repro.data.synthetic import MixtureSpec, gaussian_mixture

    cfg = dataclasses.replace(sns_paper.CANCER_1M, seed=seed)
    # learning rate N / early exaggeration (Belkina et al. 2019, the
    # large-N default of openTSNE and FIt-SNE): at 10^6 points the
    # 200 default barely moves the embedding in a few tens of iterations
    tcfg = tsne.TsneConfig(dims=2, n_iter=n_iter,
                           adaptive_interval=PHASE_B_STAGE)
    tcfg = dataclasses.replace(
        tcfg, learning_rate=n_reps / tcfg.early_exaggeration)
    ecfg = pipeline.resolve_embed_cfg(cfg, tsne_cfg=tcfg)
    k = ecfg.knn or int(3 * ecfg.perplexity)
    cut = "" if n_reps == PHASE_B_REPS else f" (CUT from {PHASE_B_REPS:,})"
    log(f"[B] config CANCER_1M: {ecfg.backend} tSNE, kNN {cfg.embed_knn_method}"
        f" k={k}, G {ecfg.grid_size}..{ecfg.grid_max} (cell spacing "
        f"{ecfg.grid_interval}), {n_iter} iterations in stages of "
        f"{PHASE_B_STAGE}, learning rate {ecfg.learning_rate:g}")
    log(f"[B] sizes: {n_reps:,} representatives{cut}, 8-D, "
        f"E = 2*N*k = {2 * n_reps * k:,} edges")
    log(f"[B] tiers (auto): {json.dumps(tiers(jax))}; on path: "
        f"{on_path(cfg)}")

    t0 = time.perf_counter()
    x, _ = gaussian_mixture(n_reps, MixtureSpec(dims=8), seed=seed + 3)
    w = np.random.default_rng(seed + 4).integers(1, 64, n_reps)
    x, w = jnp.asarray(x), jnp.asarray(w.astype(np.float32))
    log(f"[B] set-up: data {time.perf_counter() - t0:.3f} s")

    with meter.span() as s:
        emb, kl = pipeline.embed_points(cfg, jax.random.key(seed + 5), x, w,
                                        tsne_cfg=tcfg)
        emb, kl = np.asarray(emb), np.asarray(kl)
    span = float(np.max(emb.max(axis=0) - emb.min(axis=0)))
    log(f"[B] embed_points: {emb.shape}, {s.line()}")
    log(f"[B] KL first {kl[0]:.6f} last {kl[-1]:.6f}; final span "
        f"{span:.3f} -> G {tsne._grid_for_span(span, ecfg.grid_size, ecfg)}")
    check(emb.shape == (n_reps, 2), f"embedding shape {emb.shape}")
    check(bool(np.isfinite(emb).all()), "embedding has non-finite values")
    check(bool(np.isfinite(kl).all()), "KL trace has non-finite values")
    check(float(kl[-1]) < float(kl[0]),
          f"KL did not go down: {kl[0]} -> {kl[-1]}")


# ------------------------------------------------------------ four chips
def site_stream(jnp, jax, seed: int, batch: int, num_batches: int):
    """``shard_fn(site, b)`` for ``geo.geo_extract_from_shards``: each
    site draws its own batches of the 8-D mixture on its device (30 %
    uniform background, 10 Gaussian clusters, std 0.02, in [0, 1]^8)."""
    from repro.data.synthetic import MixtureSpec

    spec = MixtureSpec(dims=8)
    centers = jnp.asarray(spec.centers(seed), jnp.float32)
    base = jax.random.key(seed)

    def shard_fn(site, b):
        kk = jax.random.split(
            jax.random.fold_in(base, site * num_batches + b), 4)
        comp = jax.random.randint(kk[0], (batch,), 0, spec.n_clusters)
        pts = centers[comp] + spec.cluster_std * jax.random.normal(
            kk[1], (batch, spec.dims))
        bg = jax.random.uniform(kk[2], (batch,)) < spec.background_frac
        pts = jnp.where(bg[:, None],
                        jax.random.uniform(kk[3], (batch, spec.dims)), pts)
        return jnp.clip(pts, 0.0, 1.0), None
    return shard_fn


def four_chips(jax, meter, seed: int) -> None:
    import jax.numpy as jnp

    from repro.configs import sns_paper
    from repro.core import heavy_hitters as hh_mod
    from repro.core import pipeline, quantize, replicas
    from repro.core import sketch as sketch_mod
    from repro.core import stream as stream_mod
    from repro.core import candidates as cand_mod
    from repro.core.umap import UmapConfig

    sites = FOUR_CHIP_SITES
    check(len(jax.devices()) >= sites,
          f"--chips {sites} needs {sites} devices, JAX sees "
          f"{len(jax.devices())}")
    devs = jax.devices()[:sites]
    mesh = jax.make_mesh((sites,), ("data",), devices=devs,
                         axis_types=(jax.sharding.AxisType.Auto,))
    check(len({d.id for d in mesh.devices.flat}) == sites,
          "mesh does not span distinct devices")
    cfg = dataclasses.replace(sns_paper.CANCER, seed=seed)
    batch = cfg.ingest_chunk
    n_total = sites * FOUR_CHIP_BATCHES * batch
    grid = quantize.GridSpec(dims=8, bins=cfg.bins, lo=(0.0,) * 8,
                             hi=(1.0,) * 8)
    shard_fn = site_stream(jnp, jax, seed, batch, FOUR_CHIP_BATCHES)
    log(f"[4] config CANCER, {sites} sites, one per chip "
        f"({[d.id for d in mesh.devices.flat]}), {FOUR_CHIP_BATCHES} "
        f"batches of {batch:,} per site = {n_total:,} points; embed mesh "
        f"of {sites}")
    log(f"[4] tiers (auto): {json.dumps(tiers(jax))}; on path: "
        f"{on_path(cfg)}")

    # one UMAP epoch: what is compared is a per-step quantity, since the
    # optimizer's trajectories decohere over many epochs
    ucfg = UmapConfig(n_epochs=1)
    cfg4 = dataclasses.replace(cfg, embed_mesh=sites)
    with meter.span() as s:
        res = pipeline.run_streaming(cfg4, grid=grid, mesh=mesh,
                                     shard_fn=shard_fn,
                                     num_batches=FOUR_CHIP_BATCHES,
                                     umap_cfg=ucfg)
        emb4 = np.asarray(res.embedding)
    log(f"[4] run_streaming on {sites} chips: {emb4.shape[0]:,} reps -> "
        f"{emb4.shape}, {s.line()}")
    check(bool(np.isfinite(emb4).all()), "4-chip embedding non-finite")
    check(len(res.hh.key_hi.sharding.device_set) == sites,
          "heavy hitters not replicated over the 4-chip mesh")

    # the same four site streams, folded one after another on one chip
    pool = cfg.candidate_pool or 2 * cfg.top_k
    sk0 = sketch_mod.init(jax.random.key(cfg.seed), cfg.rows, cfg.log2_cols)

    @jax.jit
    def fold_site(site):
        def step(st, b):
            pts, mask = shard_fn(site, b)
            return stream_mod.ingest_step(st, grid, pts, mask=mask), ()
        st, _ = jax.lax.scan(step, stream_mod.from_sketch(sk0, pool),
                             jnp.arange(FOUR_CHIP_BATCHES, dtype=jnp.int32))
        return st

    with meter.span() as s:
        with jax.default_device(devs[0]):
            states = [fold_site(jnp.int32(i)) for i in range(sites)]
            table = sum(st.sketch.table for st in states)
            merged = sketch_mod.CountSketch(table=table,
                                            params=sk0.params)
            hh1 = hh_mod.from_candidates(
                merged, cand_mod.concat(*(st.cands for st in states)),
                cfg.top_k)
            total1 = float(sum(st.count for st in states))
            hh1 = jax.tree_util.tree_map(np.asarray, hh1)
    log(f"[4] same stream on one chip: {s.line()}")
    same = all(np.array_equal(np.asarray(a), b)
               for a, b in zip(res.hh, hh1))
    n_live = int(np.asarray(res.hh.mask).sum())
    log(f"[4] heavy hitters: {n_live:,} live, bit-identical {same}; "
        f"counts {float(np.asarray(res.hh.count).sum()):.0f} / "
        f"stream {total1:.0f}")
    check(total1 == float(n_total), f"one-chip fold counted {total1}")
    check(same, "4-site heavy hitters differ from the one-chip fold")

    # embed equivalence: the same representatives (pipeline.embed_stage's
    # key discipline) embedded on one device
    krep, kembed = jax.random.split(jax.random.key(cfg.seed + 1))
    reps = replicas.make_representatives(
        krep, grid, res.hh, scheme=cfg.replica_scheme,
        max_replicas=cfg.max_replicas, jitter_frac=cfg.jitter_frac)
    pts, w, _ = replicas.compact(reps)
    with meter.span() as s:
        u1, _ = pipeline.embed_points(cfg, kembed, jnp.asarray(pts),
                                      jnp.asarray(w), umap_cfg=ucfg)
        u1 = np.asarray(u1)
    check(u1.shape == emb4.shape, f"shapes {u1.shape} vs {emb4.shape}")
    scale = max(1.0, float(np.abs(u1).max()))
    dev = float(np.abs(u1 - emb4).max())
    log(f"[4] one UMAP epoch, 1 vs {sites} devices: max |diff| {dev:.3e} "
        f"(tolerance {UMAP_EPOCH1_TOL * scale:.3e}), {s.line()}")
    check(dev <= UMAP_EPOCH1_TOL * scale,
          f"sharded epoch deviates by {dev} > {UMAP_EPOCH1_TOL * scale}")


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--points", type=int, default=PHASE_A_POINTS,
                    help="phase A stream length (a cut is printed)")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repository source at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro import compile_cache

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU; JAX sees {devs[0].platform} devices",
              file=sys.stderr)
        return 2
    log(f"[chip] {len(devs)} x {devs[0].device_kind}; compile cache "
        f"{compile_cache.enable()}")
    meter = CompileMeter(jax)
    if args.chips == 4:
        four_chips(jax, meter, args.seed)
    else:
        phase_a(jax, meter, args.points, args.seed)
        phase_b(jax, meter, PHASE_B_REPS, PHASE_B_ITERS, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
