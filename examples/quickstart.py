"""Quickstart: Sketch-and-Scale on a synthetic clustered point cloud.

    PYTHONPATH=src python examples/quickstart.py [--n 200000] [--tsne]

Runs the paper's full Fig.-1 pipeline on one host: quantize → Count
Sketch → heavy hitters → weighted jittered representatives → UMAP (or
tSNE) → cluster summary.  Prints coverage and HH statistics, and writes
the 2-D embedding to /tmp/sns_embedding.csv.

Kernel tiers: every Pallas call site dispatches through
`repro.kernels.registry`, picking the best tier the current backend
supports (`SnsConfig.kernel_mode="auto"`, overridable per run or via
the `SNS_KERNEL_MODE` env var):

    tier       | what runs                        | where
    -----------+----------------------------------+--------------------
    compiled   | Mosaic/Triton-compiled Pallas    | TPU/GPU only
    interpret  | Python-level Pallas execution    | any backend
    xla        | pure-jnp reference               | any backend

Auto-resolution walks compiled → interpret → xla; the sorted-COO
segment-reduce prefers its XLA cumsum on CPU (nothing beats it there)
while the fused kernel takes over on accelerators.  Force a tier with
e.g. ``SNS_KERNEL_MODE=xla python examples/quickstart.py``.

This is the one-shot front-end.  For data that keeps arriving, the
long-lived service API (`core.service.SnsService`) wraps the same
stages behind `update(chunks)` (incremental ingest), `refresh()`
(warm-start re-embed from the previous coordinates), and
`transform(queries)` (batched out-of-sample placement, no optimizer) —
see examples/sns_service.py.  (examples/serve.py and launch/serve.py
are the LM-stack servers, unrelated to the SnS pipeline.)
"""
import argparse
import sys

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "src")

from repro.core import pipeline                               # noqa: E402
from repro.core.tsne import TsneConfig                        # noqa: E402
from repro.core.umap import UmapConfig                        # noqa: E402
from repro.data import gaussian_mixture                       # noqa: E402
from repro.data.synthetic import MixtureSpec                  # noqa: E402


def main():
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--clusters", type=int, default=8)
    ap.add_argument("--tsne", action="store_true")
    ap.add_argument("--embed-backend", default="dense",
                    choices=("dense", "tiled", "pallas", "sparse"),
                    help="tSNE gradient backend; 'sparse' (kNN attraction "
                         "+ FFT grid repulsion) is the 10^5+ reps regime")
    ap.add_argument("--knn-method", default="auto",
                    choices=("auto", "exact", "ann"),
                    help="kNN build for the embed stage: 'ann' is the "
                         "sub-quadratic approximate engine (sketch-native "
                         "bucketing + NN-descent, recall >= 0.9); 'auto' "
                         "switches to it past ~65k representatives")
    ap.add_argument("--top-k", type=int, default=512)
    args = ap.parse_args()

    spec = MixtureSpec(dims=6, n_clusters=args.clusters,
                       cluster_std=0.015, background_frac=0.3)
    pts, labels = gaussian_mixture(args.n, spec, seed=0)
    print(f"[data] {args.n} points, {args.clusters} clusters + 30% "
          f"uniform background, D={spec.dims}")

    cfg = pipeline.SnsConfig(
        bins=16, rows=8, log2_cols=14, top_k=args.top_k,
        embedder="tsne" if args.tsne else "umap", max_replicas=4,
        embed_backend=args.embed_backend,
        embed_knn_method=args.knn_method)
    res = pipeline.run(
        cfg, jnp.asarray(pts),
        tsne_cfg=TsneConfig(n_iter=250),
        umap_cfg=UmapConfig(n_neighbors=10, n_epochs=200))

    live = int(np.asarray(res.hh.mask).sum())
    top = float(np.asarray(res.hh.count)[0])
    print(f"[sketch] {cfg.rows}x{1 << cfg.log2_cols} Count Sketch")
    print(f"[hh] {live} heavy hitters; top cell holds {top:.0f} points; "
          f"coverage of stream = {res.coverage:.1%}")
    print(f"[embed] {res.embedding.shape[0]} representatives -> "
          f"{res.embedding.shape[1]}-D via {cfg.embedder}")

    out = np.concatenate([np.asarray(res.embedding),
                          res.rep_weight[:, None]], axis=1)
    np.savetxt("/tmp/sns_embedding.csv", out, delimiter=",",
               header="x,y,weight")
    print("[out] /tmp/sns_embedding.csv")


if __name__ == "__main__":
    main()
