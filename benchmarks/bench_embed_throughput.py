"""Embed throughput: tSNE gradient iterations/sec + UMAP epochs/sec.

The PR-4 tentpole claim, measured on the steady-state iteration the
optimizer's ``fori_loop`` actually runs:

* ``dense``  — the classic O(N²)-memory matmul gradient (only timed while
  its (N, N) buffers fit, ``--dense-max``).
* ``tiled``  — the pure-XLA block-streamed exact gradient: O(block·N)
  memory but still O(N²) work per iteration.
* ``sparse`` — kNN-restricted attraction (fixed-shape COO, scatter-free
  sorted-row reduction) + FFT grid repulsion: O(N·k + G²·log G) per
  iteration.  This is what turns N = 10⁵–10⁶ representative embeddings
  from hours into minutes on CPU.

And the PR-5 claim, measured the same way on the UMAP epoch:

* ``umap_scatter``     — the PR-4 epoch-batched SGD epoch, frozen
  VERBATIM below: per-edge forces reduced into per-point deltas by two
  ``.at[].add`` scatters over E = N·k edges (XLA CPU scatter walks
  updates serially).
* ``umap_scatterfree`` — the live ``umap.epoch_delta``: identical per-
  edge math, reduction via the shared sorted-COO cumsum core
  (``repro.core.coo``), zero scatter primitives.  The bidirectional edge
  layout is built once at setup, outside the timed region, exactly as
  ``optimize_embedding`` builds it outside its ``fori_loop``.

Setup costs (perplexity calibration, the one-off O(N²·D) kNN build, the
edge-layout sorts) are excluded: they are paid once, not per iteration,
and the exact backends get synthetic calibration stats for the same
reason.  The sparse COO / UMAP edge set is drawn with a uniformly random
topology — iteration cost depends only on the edge COUNT, so this times
the same work as a real graph while letting the bench scale past the
point where the kNN build dominates wall-clock.  Variants are timed in
interleaved rounds (median-of-3 per variant) so machine drift cannot
bias the ratios.

    PYTHONPATH=src python -m benchmarks.bench_embed_throughput \
        --sizes 16384,65536,262144 --json-out BENCH_embed_throughput.json

Emits a JSON trajectory (default path: BENCH_embed_throughput.json at the
repo root — the repo's tracked iterations/sec baseline); ``run()``
returns it as a string for benchmarks/run.py.
"""
from __future__ import annotations

import argparse
import json
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (emit_json, interleaved_medians,
                               repo_root_json)
from repro.core import coo, neighbors, tsne, umap
from repro.core.tsne import PointStats, SparseP

DEFAULT_JSON = repo_root_json("BENCH_embed_throughput.json")


def synthetic_stats(n: int, rng) -> PointStats:
    """Plausible calibration stats without the calibration pass."""
    beta = jnp.asarray(rng.uniform(0.5, 2.0, n).astype(np.float32))
    shift = jnp.zeros((n,), jnp.float32)
    zp = jnp.asarray(rng.uniform(5.0, 50.0, n).astype(np.float32))
    w = jnp.full((n,), 1.0 / n, jnp.float32)
    return PointStats(beta=beta, shift=shift, zp=zp, w=w)


def synthetic_sparse_p(n: int, k: int, rng) -> SparseP:
    """Random-topology COO with the real layout (symmetric closure of a
    k-out graph, deduped + sorted + row bounds): E = 2·N·k edges."""
    srcf = np.repeat(np.arange(n, dtype=np.int32), k)
    dstf = rng.integers(0, n, size=n * k).astype(np.int32)
    src = jnp.asarray(np.concatenate([srcf, dstf]))
    dst = jnp.asarray(np.concatenate([dstf, srcf]))
    val = jnp.full((2 * n * k,), 0.5 / (n * k), jnp.float32)
    s, d, v = neighbors.dedupe_edges(src, dst, val)
    return SparseP(src=s, dst=d, val=v, bounds=neighbors.row_bounds(s, n))


def synthetic_umap_edges(n: int, k: int, rng
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Random-topology UMAP edge set with the real layout: the fuzzy-set
    edge list is (rows repeated k times, kNN columns) — src-sorted by
    construction — with memberships in (0, 1]."""
    src = np.repeat(np.arange(n, dtype=np.int32), k)
    dst = rng.integers(0, n, size=n * k).astype(np.int32)
    memb = rng.uniform(0.05, 1.0, size=n * k).astype(np.float32)
    return jnp.asarray(np.stack([src, dst], axis=1)), jnp.asarray(memb)


# --------------------------------------------------------------------------
# The PR-4 UMAP epoch reduction, frozen VERBATIM (modulo function
# packaging): per-edge attraction/repulsion reduced into per-point deltas
# by two `.at[].add` scatters.  The live `umap.epoch_delta` has since been
# rebuilt on the sorted-COO cumsum core, so reconstructing the old epoch
# from it would silently flatter the baseline.  Given the same `kneg` and
# an src-sorted edge list this computes the same delta as the live epoch
# up to summation order (tests/test_umap_scatter_free.py pins the
# trajectory equivalence).
# --------------------------------------------------------------------------

def umap_scatter_epoch_delta(y, kneg, src, dst, memb_n, a, b, neg_rate):
    e = src.shape[0]
    n = y.shape[0]
    ys, yd = y[src], y[dst]
    d2 = jnp.sum((ys - yd) ** 2, axis=1)
    grad_coef = (-2.0 * a * b * d2 ** (b - 1.0)
                 / (1.0 + a * d2 ** b))
    grad_coef = jnp.where(d2 > 0, grad_coef, 0.0)
    att = jnp.clip(grad_coef[:, None] * (ys - yd), -4.0, 4.0) \
        * memb_n[:, None]
    neg = jax.random.randint(kneg, (e, neg_rate), 0, n)
    valid = (neg != src[:, None]) & (neg != dst[:, None])
    yn = y[neg]
    dn2 = jnp.sum((ys[:, None, :] - yn) ** 2, axis=2)
    rep_coef = (2.0 * b) / ((0.001 + dn2) * (1.0 + a * dn2 ** b))
    rep = jnp.clip(rep_coef[..., None] * (ys[:, None, :] - yn),
                   -4.0, 4.0) * memb_n[:, None, None]
    rep = jnp.where(valid[..., None], rep, 0.0)
    delta = jnp.zeros_like(y)
    delta = delta.at[src].add(att + jnp.sum(rep, axis=1))
    delta = delta.at[dst].add(-att)
    return delta


def run(sizes: Sequence[int] = (16384, 65536, 262144), block: int = 512,
        knn: int = 90, grid: int = 128, dense_max: int = 16384,
        tiled_max: int = 65536, iters: int = 3, umap_knn: int = 15,
        neg_rate: int = 5,
        json_out: Optional[str] = DEFAULT_JSON) -> str:
    rng = np.random.default_rng(0)
    records = []
    for n in sizes:
        x = jnp.asarray(rng.normal(size=(n, 8)).astype(np.float32))
        y = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
        stats = synthetic_stats(n, rng)
        sp = synthetic_sparse_p(n, knn, rng)

        sparse_step = jax.jit(
            lambda y_: tsne.sparse_grad(y_, sp, 1.0, grid_size=grid)[0])
        drivers = {
            "sparse": lambda: jax.block_until_ready(sparse_step(y))}
        skipped = {}
        for backend, cap in (("tiled", tiled_max), ("dense", dense_max)):
            if n > cap:
                skipped[backend] = (f"O(N²) per-iteration cost at N={n} — "
                                    f"over --{backend}-max={cap}")
                continue
            step = jax.jit(lambda y_, _b=backend: tsne.embedding_grad(
                x, y_, stats, 1.0, backend=_b, block=block)[0])
            drivers[backend] = \
                lambda _s=step: jax.block_until_ready(_s(y))

        # UMAP epoch: frozen scatter baseline vs live scatter-free epoch,
        # same per-edge math and the same negative-sample key, timed on
        # the steady-state epoch (edge layout built outside, like the
        # optimizer's own setup)
        edges, memb = synthetic_umap_edges(n, umap_knn, rng)
        a, b = umap.fit_ab(1.0, 0.1)
        memb_n = memb / jnp.maximum(jnp.max(memb), 1e-12)
        layout, order = coo.edge_layout(edges[:, 0], edges[:, 1], n)
        memb_s = memb_n[order]
        usrc, udst = edges[:, 0], edges[:, 1]
        kneg = jax.random.key(1)
        scatter_step = jax.jit(lambda y_, k_: y_ + umap_scatter_epoch_delta(
            y_, k_, usrc, udst, memb_n, a, b, neg_rate))
        free_step = jax.jit(lambda y_, k_: y_ + umap.epoch_delta(
            y_, layout, memb_s, k_, a, b, neg_rate))
        drivers["umap_scatter"] = \
            lambda: jax.block_until_ready(scatter_step(y, kneg))
        drivers["umap_scatterfree"] = \
            lambda: jax.block_until_ready(free_step(y, kneg))

        times = interleaved_medians(drivers, iters=iters)
        rec = {"bench": "embed_throughput", "n": n, "knn": knn,
               "grid": grid, "block": block,
               "edges": int(sp.src.shape[0]),
               "umap_knn": umap_knn, "neg_rate": neg_rate,
               "umap_edges": int(usrc.shape[0])}
        for backend in ("dense", "tiled", "sparse"):
            ips = 1.0 / times[backend] if backend in times else None
            rec[f"{backend}_ips"] = ips
            if backend in skipped:
                rec[f"{backend}_skipped"] = skipped[backend]
        if rec["tiled_ips"]:
            rec["speedup_sparse_vs_tiled"] = \
                rec["sparse_ips"] / rec["tiled_ips"]
        rec["umap_scatter_eps"] = 1.0 / times["umap_scatter"]
        rec["umap_scatterfree_eps"] = 1.0 / times["umap_scatterfree"]
        rec["speedup_umap_scatterfree_vs_scatter"] = \
            rec["umap_scatterfree_eps"] / rec["umap_scatter_eps"]
        records.append(rec)
        fmt = lambda v: f"{v:8.3f}" if v else "       -"
        print(f"# embed_throughput N={n:7d} k={knn} G={grid} "
              f"dense={fmt(rec['dense_ips'])} tiled={fmt(rec['tiled_ips'])} "
              f"sparse={fmt(rec['sparse_ips'])} iters/s  "
              f"sparse/tiled={rec.get('speedup_sparse_vs_tiled', '-')}",
              flush=True)
        print(f"#                  N={n:7d} k={umap_knn} R={neg_rate} "
              f"umap_scatter={fmt(rec['umap_scatter_eps'])} "
              f"umap_scatterfree={fmt(rec['umap_scatterfree_eps'])} "
              f"epochs/s  scatterfree/scatter="
              f"{rec['speedup_umap_scatterfree_vs_scatter']:.1f}",
              flush=True)

    common = [r for r in records if r.get("speedup_sparse_vs_tiled")]
    return emit_json({
        "bench": "embed_throughput",
        "speedup_sparse_vs_tiled_at_max_common_n":
            common[-1]["speedup_sparse_vs_tiled"] if common else None,
        "speedup_umap_scatterfree_vs_scatter_at_max_n":
            records[-1]["speedup_umap_scatterfree_vs_scatter"]
            if records else None,
        "records": records}, json_out)


def main() -> None:
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sizes", default="16384,65536,262144")
    ap.add_argument("--block", type=int, default=512)
    ap.add_argument("--knn", type=int, default=90,
                    help="sparse fan-out k (default 3·perplexity at the "
                         "paper's perplexity 30)")
    ap.add_argument("--grid", type=int, default=128)
    ap.add_argument("--dense-max", type=int, default=16384,
                    help="largest N at which the dense backend is timed")
    ap.add_argument("--tiled-max", type=int, default=65536,
                    help="largest N at which the tiled backend is timed")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--umap-knn", type=int, default=15,
                    help="UMAP edge fan-out k (E = N·k edges per epoch)")
    ap.add_argument("--neg-rate", type=int, default=5,
                    help="UMAP negative samples per edge")
    ap.add_argument("--json-out", default=DEFAULT_JSON)
    args = ap.parse_args()
    sizes = tuple(int(s) for s in args.sizes.split(","))
    print(run(sizes=sizes, block=args.block, knn=args.knn, grid=args.grid,
              dense_max=args.dense_max, tiled_max=args.tiled_max,
              iters=args.iters, umap_knn=args.umap_knn,
              neg_rate=args.neg_rate, json_out=args.json_out))


if __name__ == "__main__":
    main()
