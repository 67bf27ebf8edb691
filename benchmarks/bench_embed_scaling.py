"""Embed-stage scaling: dense vs tiled tSNE gradient, time + memory vs N.

Demonstrates the tentpole claim: the dense backend's per-iteration peak
buffer grows as 3·N²·4 bytes (the cliff that pinned the paper at
N ≈ 2·10⁴ representatives), while the tiled backend's peak temp stays at
block·N — a flat line in N for fixed work per row.

Peak buffer sizes are measured *statically* by walking the jaxpr of one
gradient step and taking the largest intermediate — no allocation needed,
so the dense trajectory can be reported past the point where it would
OOM.  Iteration times are wall-clock (dense only attempted while its
buffers fit, ``--dense-max``).

    PYTHONPATH=src python -m benchmarks.bench_embed_scaling \
        --sizes 8192,16384,32768,65536 --json-out BENCH_embed_scaling.json

Also times the chunked UMAP kNN stage at each N (the other former O(N²)
buffer).  Emits a JSON trajectory (default path: BENCH_embed_scaling.json
at the repo root, the tracked BENCH_*.json convention); ``run()`` returns
it as a string for benchmarks/run.py.
"""
from __future__ import annotations

import argparse
import json
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# peak_buffer_bytes / iter_jaxpr_avals moved to benchmarks.common (shared
# with bench_ingest_scaling); re-exported here for callers of this module.
from benchmarks.common import (emit_json, iter_jaxpr_avals,  # noqa: F401
                               peak_buffer_bytes, repo_root_json, time_fn)
from benchmarks.bench_embed_throughput import (synthetic_sparse_p,
                                               synthetic_stats)
from repro.core import tsne, umap
from repro.core.tsne import PointStats  # noqa: F401  (re-export)

DEFAULT_JSON = repo_root_json("BENCH_embed_scaling.json")


def run(sizes: Sequence[int] = (8192, 16384, 32768, 65536),
        dense_max: int = 16384, block: int = 512, dims_hi: int = 8,
        iters: int = 2, umap_k: int = 15, sparse_k: int = 32,
        sparse_grid: int = 128,
        json_out: Optional[str] = DEFAULT_JSON) -> str:
    rng = np.random.default_rng(0)
    records = []
    for n in sizes:
        x = jnp.asarray(rng.normal(size=(n, dims_hi)).astype(np.float32))
        y = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
        stats = synthetic_stats(n, rng)
        sp = synthetic_sparse_p(n, sparse_k, rng)
        for backend in ("dense", "tiled", "sparse"):
            if backend == "sparse":
                def grad(y_):
                    return tsne.sparse_grad(y_, sp, 1.0,
                                            grid_size=sparse_grid)[0]
            else:
                def grad(y_, _backend=backend):
                    return tsne.embedding_grad(x, y_, stats, 1.0,
                                               backend=_backend,
                                               block=block)[0]

            rec = {"stage": "tsne_grad", "backend": backend, "n": n,
                   "block": block,
                   "peak_buffer_bytes": peak_buffer_bytes(grad, y)}
            if backend == "dense" and n > dense_max:
                rec["iter_time_s"] = None
                rec["skipped"] = (f"dense O(N²) buffers at N={n} "
                                  f"(~{rec['peak_buffer_bytes'] / 1e9:.1f} GB)"
                                  " — over --dense-max")
            else:
                jitted = jax.jit(grad)
                rec["iter_time_s"] = time_fn(jitted, y, warmup=1, iters=iters)
            records.append(rec)
            print(f"# tsne_grad {backend:6s} N={n:6d} "
                  f"peak={rec['peak_buffer_bytes'] / 1e6:10.1f} MB "
                  f"t={rec['iter_time_s']}", flush=True)

        def knn(x_):
            return umap.knn_graph(x_, umap_k, block=block)

        rec = {"stage": "umap_knn", "backend": "tiled", "n": n,
               "block": block, "peak_buffer_bytes": peak_buffer_bytes(knn, x),
               "iter_time_s": time_fn(jax.jit(knn), x, warmup=1, iters=1)}
        records.append(rec)
        print(f"# umap_knn  tiled  N={n:6d} "
              f"peak={rec['peak_buffer_bytes'] / 1e6:10.1f} MB "
              f"t={rec['iter_time_s']:.3f}", flush=True)

    return emit_json({"bench": "embed_scaling", "records": records},
                     json_out)


def main() -> None:
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sizes", default="8192,16384,32768,65536")
    ap.add_argument("--dense-max", type=int, default=16384,
                    help="largest N at which the dense backend is timed")
    ap.add_argument("--block", type=int, default=512)
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--json-out", default=DEFAULT_JSON)
    args = ap.parse_args()
    sizes = tuple(int(s) for s in args.sizes.split(","))
    print(run(sizes=sizes, dense_max=args.dense_max, block=args.block,
              iters=args.iters, json_out=args.json_out))


if __name__ == "__main__":
    main()
