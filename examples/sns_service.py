"""Online SnS service: ingest → serve → drift → warm refresh → transform.

    PYTHONPATH=src python examples/sns_service.py [--n 100000] [--tsne]

The SnS counterpart of the LM-stack servers (`examples/serve.py` /
`launch/serve.py` serve language models; THIS is the paper's pipeline as
a service, ROADMAP item 3).  One episode of the serving loop:

  1. `update(chunks)`   — fold a stream into the live ingest state
                          (linear sketch: no history re-read);
  2. `refresh()`        — heavy hitters → representatives → embedding
                          (cold the first time);
  3. more `update()`    — absorb a drift batch; `needs_refresh()` trips
                          once pending mass crosses the drift gate;
  4. `refresh()` again  — warm: returning cells seeded at their old
                          coordinates, ~10× fewer optimizer iterations;
  5. `transform(q)`     — out-of-sample queries placed against the
                          frozen embedding, no optimizer, batched.

Prints the absorption rate, warm-start match statistics, and transform
throughput; writes the served embedding to /tmp/sns_service_embedding.csv.
"""
import argparse
import sys
import time

import numpy as np

sys.path.insert(0, "src")

from repro.core import pipeline, quantize                     # noqa: E402
from repro.core.service import ServiceConfig, SnsService      # noqa: E402
from repro.core.tsne import TsneConfig                        # noqa: E402
from repro.core.umap import UmapConfig                        # noqa: E402
from repro.data import gaussian_mixture                       # noqa: E402
from repro.data.synthetic import MixtureSpec                  # noqa: E402


def main():
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--drift-frac", type=float, default=0.08)
    ap.add_argument("--dims", type=int, default=4)
    ap.add_argument("--top-k", type=int, default=512)
    ap.add_argument("--tsne", action="store_true")
    ap.add_argument("--queries", type=int, default=50_000)
    args = ap.parse_args()

    spec = MixtureSpec(dims=args.dims, n_clusters=8, cluster_std=0.05,
                       background_frac=0.1)
    base, _ = gaussian_mixture(args.n, spec, seed=0)
    drift, _ = gaussian_mixture(int(args.n * args.drift_frac), spec, seed=1)
    base, drift = np.asarray(base, np.float32), np.asarray(drift, np.float32)

    cfg = pipeline.SnsConfig(
        bins=16, rows=8, log2_cols=14, top_k=args.top_k,
        embedder="tsne" if args.tsne else "umap",
        embed_backend="dense", max_replicas=4)
    # the grid is the service's fixed frame of reference (cell keys must
    # be comparable across refreshes) — fit it on what we expect to see
    grid = quantize.fit_grid(np.concatenate([base, drift]), cfg.bins)
    svc = SnsService(cfg, grid,
                     tsne_cfg=TsneConfig(dims=2, n_iter=400),
                     umap_cfg=UmapConfig(dims=2, n_epochs=200),
                     service_cfg=ServiceConfig())

    stats = svc.update(np.array_split(base, 8))
    print(f"[update]  absorbed {stats['points']:.0f} points at "
          f"{stats['points_per_sec']:,.0f} pts/s")

    t0 = time.perf_counter()
    cold = svc.refresh()
    print(f"[refresh] cold: {cold.embedding.shape[0]} reps embedded in "
          f"{cold.n_iters} iters ({time.perf_counter() - t0:.1f}s)")

    stats = svc.update(drift)
    print(f"[update]  drift {stats['points']:.0f} points -> pending "
          f"{stats['pending_fraction']:.1%}, "
          f"needs_refresh={stats['needs_refresh']}")

    t0 = time.perf_counter()
    warm = svc.refresh()
    print(f"[refresh] warm: matched {warm.n_matched}, new {warm.n_new}, "
          f"{warm.n_iters} iters ({time.perf_counter() - t0:.1f}s)")

    q, _ = gaussian_mixture(args.queries, spec, seed=2)
    q = np.asarray(q, np.float32)
    svc.transform(q[:1024])                       # compile
    t0 = time.perf_counter()
    y = svc.transform(q)
    dt = time.perf_counter() - t0
    print(f"[transform] {len(q):,} queries in {dt * 1e3:.1f} ms "
          f"({len(q) / dt:,.0f} q/s)")

    out = "/tmp/sns_service_embedding.csv"
    np.savetxt(out, np.column_stack([np.asarray(warm.embedding),
                                     warm.weights]),
               delimiter=",", header="y0,y1,weight", comments="")
    print(f"wrote served embedding -> {out}")


if __name__ == "__main__":
    main()
