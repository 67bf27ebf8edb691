"""Streaming ingest: bounded-memory Sketch-and-Scale over a sharded stream.

    PYTHONPATH=src python examples/streaming_ingest.py [--n 400000]

The paper's 'single stream I/O' regime on one host: data arrives as
shard-plan batches from a ShardedLoader (over-decomposed, deterministic,
resumable) and is folded chunk-by-chunk through core.stream.IngestState —
a Count Sketch plus a bounded candidate reservoir.  No stage ever holds
the full (N, D) array: the grid comes from a chunked min/max pass, the
sketch stage's working set is O(ingest_chunk + candidate_pool), and only
the heavy-hitter representatives reach the embedder.
"""
import argparse
import sys

import numpy as np

sys.path.insert(0, "src")

from repro.core import pipeline                               # noqa: E402
from repro.core.umap import UmapConfig                        # noqa: E402
from repro.data.loader import ShardPlan                       # noqa: E402
from repro.data.synthetic import (MixtureSpec,                # noqa: E402
                                  clustered_points_sharded)


def main():
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=400_000)
    ap.add_argument("--shards", type=int, default=32)
    ap.add_argument("--top-k", type=int, default=512)
    ap.add_argument("--chunk", type=int, default=16_384)
    ap.add_argument("--superbatch", type=int, default=8,
                    help="chunks folded per dispatch (1 = per-chunk)")
    args = ap.parse_args()

    spec = MixtureSpec(dims=6, n_clusters=8, cluster_std=0.015,
                       background_frac=0.3)
    per_shard = args.n // args.shards
    plan = ShardPlan(num_shards=args.shards, num_hosts=1)
    chunks = pipeline.chunks_from_loader(
        plan, host=0,
        make_batch=lambda shard, b: clustered_points_sharded(
            shard, per_shard, spec, seed=7))
    print(f"[stream] {args.shards} shards x {per_shard} points; no host "
          f"ever holds the {args.n}x{spec.dims} array")

    cfg = pipeline.SnsConfig(bins=16, rows=8, log2_cols=14,
                             top_k=args.top_k, candidate_pool=4 * args.top_k,
                             ingest_chunk=args.chunk,
                             ingest_superbatch=args.superbatch,
                             max_replicas=4)
    res = pipeline.run_streaming(
        cfg, chunks, umap_cfg=UmapConfig(n_neighbors=10, n_epochs=200))
    if res.hh_error_bound == 0.0:
        print("[hh] reservoir never evicted — heavy hitters exact "
              "up to the pool size")
    else:
        print(f"[hh] space-saving watermark {res.hh_error_bound:.0f} "
              f"(largest count ever evicted from the reservoir)")

    live = int(np.asarray(res.hh.mask).sum())
    state_bytes = (cfg.rows * (1 << cfg.log2_cols) * 4          # table
                   + (cfg.candidate_pool or 2 * cfg.top_k) * 13  # reservoir
                   + cfg.ingest_chunk * spec.dims * 4)           # chunk
    print(f"[ingest] working set ≈ {state_bytes / 2**20:.1f} MiB "
          f"(vs {args.n * spec.dims * 4 / 2**20:.0f} MiB resident)")
    print(f"[hh] {live} heavy hitters, coverage {res.coverage:.1%} "
          f"of the {args.n}-point stream")
    print(f"[embed] {res.embedding.shape[0]} representatives -> "
          f"{res.embedding.shape[1]}-D via {cfg.embedder}")

    out = np.concatenate([np.asarray(res.embedding),
                          res.rep_weight[:, None]], axis=1)
    np.savetxt("/tmp/sns_streaming_embedding.csv", out, delimiter=",",
               header="x,y,weight")
    print("[out] /tmp/sns_streaming_embedding.csv")


if __name__ == "__main__":
    main()
