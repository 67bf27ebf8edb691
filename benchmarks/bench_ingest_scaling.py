"""Ingest-stage scaling: streaming (bounded) vs one-shot sketch stage.

Demonstrates the streaming-ingest tentpole claim: the one-shot path's peak
buffer grows linearly with stream length N (it materializes all N packed
keys, then sorts them), while the streaming engine's peak buffer is a
function of (chunk, candidate_pool, sketch geometry) only — a flat line
across N = 8k → 1M.  Wall-clock ingest time stays linear in N (the
paper's Fig. 6 regime).

Peak buffers are measured *statically* by walking the jaxpr of each path
(benchmarks.common.peak_buffer_bytes) — no allocation, so the one-shot
trajectory can be reported past the point where it would stop fitting.
Streaming wall-clock drives the real jitted ``stream.ingest_chunk`` over
synthetic clustered chunks.

    PYTHONPATH=src python -m benchmarks.bench_ingest_scaling \
        --sizes 8192,65536,262144,1048576 --json-out ingest_scaling.json

Emits a JSON trajectory; ``run()`` returns it as a string for
benchmarks/run.py.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import peak_buffer_bytes, time_fn
from repro.core import heavy_hitters as hh_mod
from repro.core import quantize, sketch as sketch_mod, stream
from repro.data.synthetic import MixtureSpec, gaussian_mixture

DIMS = 6
SPEC = MixtureSpec(dims=DIMS, n_clusters=8, cluster_std=0.02,
                   background_frac=0.3)


def _grid(bins: int) -> quantize.GridSpec:
    return quantize.GridSpec(dims=DIMS, bins=bins,
                             lo=tuple([0.0] * DIMS), hi=tuple([1.0] * DIMS))


def _oneshot_peak(n: int, grid, rows: int, log2_cols: int, top_k: int,
                  pool: int) -> int:
    """Static peak buffer of the pre-refactor path: all N keys resident."""
    def oneshot(pts):
        key_hi, key_lo = quantize.points_to_keys(grid, pts)
        sk = sketch_mod.init(jax.random.key(0), rows, log2_cols)
        sk = sketch_mod.update_sorted(sk, key_hi, key_lo)
        return hh_mod.extract(sk, key_hi, key_lo, k=top_k,
                              candidate_pool=pool)

    return peak_buffer_bytes(oneshot, jnp.zeros((n, DIMS), jnp.float32))


def _streaming_peak(chunk: int, grid, rows: int, log2_cols: int,
                    pool: int) -> int:
    """Static peak buffer of one ingest step — N never appears."""
    state = stream.init(jax.random.key(0), rows, log2_cols, pool)

    def step(st, pts, mask):
        return stream.ingest_step(st, grid, pts, mask=mask)

    return peak_buffer_bytes(step, state, jnp.zeros((chunk, DIMS)),
                             jnp.ones((chunk,), bool))


def run(sizes: Sequence[int] = (8192, 65536, 262144, 1048576),
        chunk: int = 8192, bins: int = 16, rows: int = 8,
        log2_cols: int = 14, top_k: int = 256, pool: int = 512,
        oneshot_time_max: int = 262144,
        json_out: Optional[str] = None) -> str:
    grid = _grid(bins)
    records = []
    for n in sizes:
        c = min(chunk, n)
        rec = {"bench": "ingest", "n": n, "chunk": c, "pool": pool,
               "oneshot_peak_bytes": _oneshot_peak(n, grid, rows,
                                                   log2_cols, top_k, pool),
               "stream_peak_bytes": _streaming_peak(c, grid, rows,
                                                    log2_cols, pool)}

        # wall-clock: drive the real jitted donated step over the stream
        pts, _ = gaussian_mixture(n, SPEC, seed=0)

        def chunks():
            for s in range(0, n, c):
                yield pts[s:s + c]

        state = stream.init(jax.random.key(0), rows, log2_cols, pool)
        state = stream.ingest_all(state, grid, chunks(), c)  # warm the trace
        state = stream.init(jax.random.key(0), rows, log2_cols, pool)
        t0 = time.perf_counter()
        state = stream.ingest_all(state, grid, chunks(), c)
        jax.block_until_ready(state.sketch.table)
        rec["stream_ingest_s"] = time.perf_counter() - t0
        hh = hh_mod.from_candidates(state.sketch, state.cands, top_k)
        rec["coverage"] = float(jnp.sum(hh.count)) / float(state.count)

        if n <= oneshot_time_max:
            # symmetric methodology: jitted + trace-warmed, like the
            # streaming path above (compile time excluded from both)
            key_hi, key_lo = quantize.points_to_keys(grid, jnp.asarray(pts))

            @jax.jit
            def oneshot(khi, klo):
                sk = sketch_mod.init(jax.random.key(0), rows, log2_cols)
                sk = sketch_mod.update_sorted(sk, khi, klo)
                return hh_mod.extract(sk, khi, klo, k=top_k,
                                      candidate_pool=pool)

            rec["oneshot_ingest_s"] = time_fn(oneshot, key_hi, key_lo,
                                              warmup=1, iters=1)
        else:
            rec["oneshot_ingest_s"] = None

        records.append(rec)
        print(f"# ingest N={n:8d} chunk={c:6d} "
              f"stream_peak={rec['stream_peak_bytes'] / 1e6:7.2f} MB "
              f"oneshot_peak={rec['oneshot_peak_bytes'] / 1e6:7.2f} MB "
              f"t_stream={rec['stream_ingest_s']:.3f}s", flush=True)

    # flat = "peak independent of stream length": records sharing a chunk
    # size must report identical peaks (a size < chunk shrinks the chunk
    # and legitimately changes the peak, so compare within chunk groups)
    by_chunk: dict = {}
    for r in records:
        by_chunk.setdefault(r["chunk"], set()).add(r["stream_peak_bytes"])
    flat = all(len(peaks) == 1 for peaks in by_chunk.values())
    from benchmarks.common import emit_json
    return emit_json({"bench": "ingest_scaling",
                      "stream_peak_flat": flat,
                      "records": records}, json_out)


def main() -> None:
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sizes", default="8192,65536,262144,1048576")
    ap.add_argument("--chunk", type=int, default=8192)
    ap.add_argument("--bins", type=int, default=16)
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--log2-cols", type=int, default=14)
    ap.add_argument("--top-k", type=int, default=256)
    ap.add_argument("--pool", type=int, default=512)
    ap.add_argument("--oneshot-time-max", type=int, default=262144,
                    help="largest N at which the one-shot path is timed")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args()
    sizes = tuple(int(s) for s in args.sizes.split(","))
    print(run(sizes=sizes, chunk=args.chunk, bins=args.bins, rows=args.rows,
              log2_cols=args.log2_cols, top_k=args.top_k, pool=args.pool,
              oneshot_time_max=args.oneshot_time_max,
              json_out=args.json_out))


if __name__ == "__main__":
    main()
