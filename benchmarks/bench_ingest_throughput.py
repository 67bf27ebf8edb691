"""Ingest throughput: points/sec across the three chunk-fold generations.

The PR-3 tentpole claim, measured end to end on a synthetic clustered
stream (same generator as bench_ingest_scaling):

* ``twosort``  — the PR-2 per-chunk fold, reconstructed: ``update_sorted``
  sorts the chunk for the sketch, then ``merge_topk`` re-sorts pool ∪
  raw-chunk for the reservoir — every chunk pays two lexsorts over
  overlapping key material, one of them over the whole L-entry pool.
* ``fused``    — one ``sorted_runs`` per chunk feeds both the sketch
  scatter and the sort-free ``merge_runs`` (binary-search sorted merge
  against the key-sorted reservoir); still one dispatch per chunk.
* ``fused_superbatch`` — the fused fold inside ``ingest_superbatch``'s
  donated ``lax.scan`` (B chunks per dispatch) driven by the
  double-buffered ``ingest_all`` (device_put of batch b+1 overlaps the
  compute of batch b).

All three produce bit-identical heavy hitters (tests/test_fused_ingest.py);
only the points/sec differ.  Default geometry is the paper-scale heavy-
hitter extraction (top_k 20480) with the deep churn-regime reservoir
(pool = 4·top_k, the setting examples/streaming_ingest.py recommends when
the distinct-key universe exceeds the pool) and a small low-latency chunk
— the regime where the legacy path's per-chunk pool re-sort dominates and
the fused merge pays off hardest.  The three variants are timed in
interleaved rounds (median per variant) so machine drift cannot bias the
ratios.

    PYTHONPATH=src python -m benchmarks.bench_ingest_throughput \
        --sizes 65536,262144,1048576 --json-out BENCH_ingest_throughput.json

Emits a JSON trajectory (default path: BENCH_ingest_throughput.json at the
repo root — the repo's tracked points/sec baseline); ``run()`` returns it
as a string for benchmarks/run.py.
"""
from __future__ import annotations

import argparse
import functools
import json
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (emit_json, interleaved_medians,
                               repo_root_json)
from repro.core import quantize, sketch as sketch_mod, stream
from repro.core.candidates import Candidates
from repro.data.synthetic import MixtureSpec, gaussian_mixture

DIMS = 6
SPEC = MixtureSpec(dims=DIMS, n_clusters=8, cluster_std=0.02,
                   background_frac=0.3)
DEFAULT_JSON = repo_root_json("BENCH_ingest_throughput.json")


def _grid(bins: int) -> quantize.GridSpec:
    return quantize.GridSpec(dims=DIMS, bins=bins,
                             lo=tuple([0.0] * DIMS), hi=tuple([1.0] * DIMS))


# --------------------------------------------------------------------------
# The PR-2 two-sort chunk fold, frozen VERBATIM (modulo imports) so the
# baseline stays what it actually was: `update_sorted` re-sorting the chunk
# (lexsort + nonzero-RLE + deduped scatter) and `merge_topk` re-sorting
# pool ∪ raw-chunk (concat + lexsort + nonzero-RLE + top_k).  The live
# library versions of these helpers have since been rebuilt on the fused
# runs machinery, so reconstructing the old fold from them would silently
# flatter the baseline.
# --------------------------------------------------------------------------

def _pr2_update_sorted(sk, key_hi, key_lo, mask=None):
    items = key_hi.shape[0]
    v = jnp.ones((items,), sk.table.dtype)
    if mask is not None:
        v = v * mask.astype(sk.table.dtype)
    order = jnp.lexsort((key_lo, key_hi))
    shi, slo, sv = key_hi[order], key_lo[order], v[order]
    new_run = jnp.concatenate([
        jnp.ones((1,), bool),
        (shi[1:] != shi[:-1]) | (slo[1:] != slo[:-1])])
    run_id = jnp.cumsum(new_run) - 1
    run_sum = jax.ops.segment_sum(sv, run_id, num_segments=items)
    first_idx = jnp.where(new_run, size=items, fill_value=items - 1)[0]
    rhi, rlo = shi[first_idx], slo[first_idx]
    live = jnp.arange(items) < (run_id[-1] + 1)
    return sketch_mod.update(sk, rhi, rlo, values=run_sum, mask=live)


def _pr2_local_topk(key_hi, key_lo, k, values=None, mask=None):
    from repro.core.candidates import INVALID_KEY, concat, empty
    n = key_hi.shape[0]
    v = jnp.ones((n,), jnp.float32) if values is None \
        else values.astype(jnp.float32)
    if mask is not None:
        v = v * mask.astype(jnp.float32)
    order = jnp.lexsort((key_lo, key_hi))
    shi, slo, sv = key_hi[order], key_lo[order], v[order]
    new_run = jnp.concatenate([
        jnp.ones((1,), bool),
        (shi[1:] != shi[:-1]) | (slo[1:] != slo[:-1])])
    run_id = jnp.cumsum(new_run) - 1
    run_sum = jax.ops.segment_sum(sv, run_id, num_segments=n)
    first_idx = jnp.where(new_run, size=n, fill_value=n - 1)[0]
    rhi, rlo = shi[first_idx], slo[first_idx]
    num_runs = run_id[-1] + 1
    live = jnp.arange(n) < num_runs
    live &= run_sum > 0
    score = jnp.where(live, run_sum, -jnp.inf)
    kk = min(k, n)
    top_score, top_idx = jax.lax.top_k(score, kk)
    cmask = jnp.isfinite(top_score)
    out = Candidates(
        key_hi=jnp.where(cmask, rhi[top_idx], jnp.uint32(INVALID_KEY)),
        key_lo=jnp.where(cmask, rlo[top_idx], jnp.uint32(INVALID_KEY)),
        count=jnp.where(cmask, top_score, 0.0),
        mask=cmask)
    if kk < k:
        out = concat(out, empty(k - kk))
    return out


def _legacy_step(state: stream.IngestState, points, mask, *, grid):
    """The PR-2 two-sort chunk fold (what stream.ingest_step used to be)."""
    from repro.core.candidates import concat
    pool = state.cands.capacity
    n = points.shape[0]
    key_hi, key_lo = quantize.points_to_keys(grid, points)
    sk = _pr2_update_sorted(state.sketch, key_hi, key_lo, mask=mask)
    chunk_cands = Candidates(
        key_hi=key_hi, key_lo=key_lo,
        count=jnp.ones((n,), jnp.float32), mask=mask)
    both = concat(state.cands, chunk_cands)
    cands = _pr2_local_topk(both.key_hi, both.key_lo, pool,
                            values=both.count, mask=both.mask)
    inc = jnp.sum(mask.astype(jnp.float32))
    return stream.IngestState(sketch=sk, cands=cands,
                              count=state.count + inc,
                              evict_max=state.evict_max)


def _chunk_driver(step_fn, init_fn, pts, chunk: int):
    """A zero-arg callable folding the whole array chunk by chunk (a
    ragged tail is zero-padded and masked, like stream.rechunk)."""
    n, d = pts.shape

    def once():
        st = init_fn()
        for s in range(0, n, chunk):
            blk = pts[s:s + chunk]
            take = blk.shape[0]
            if take < chunk:
                blk = np.concatenate(
                    [blk, np.zeros((chunk - take, d), np.float32)])
            st = step_fn(st, jnp.asarray(blk),
                         jnp.asarray(np.arange(chunk) < take))
        jax.block_until_ready(st.sketch.table)

    return once


def run(sizes: Sequence[int] = (65536, 262144, 1048576),
        chunk: int = 4096, superbatch: int = 16, bins: int = 16,
        rows: int = 8, log2_cols: int = 16, top_k: int = 20480,
        pool: int = 0, json_out: Optional[str] = DEFAULT_JSON) -> str:
    pool = pool or 4 * top_k
    grid = _grid(bins)
    legacy_jit = jax.jit(functools.partial(_legacy_step, grid=grid),
                         donate_argnums=(0,))
    records = []
    for n in sizes:
        c = min(chunk, n)
        pts, _ = gaussian_mixture(n, SPEC, seed=0)

        def fresh():
            return stream.init(jax.random.key(0), rows, log2_cols, pool)

        def super_once():
            st = stream.ingest_all(fresh(), grid, [pts], c,
                                   superbatch=superbatch)
            jax.block_until_ready(st.sketch.table)

        times = interleaved_medians({
            "twosort": _chunk_driver(legacy_jit, fresh, pts, c),
            "fused": _chunk_driver(
                functools.partial(stream.ingest_chunk, grid=grid),
                fresh, pts, c),
            "super": super_once})
        t_two, t_fused, t_super = (times["twosort"], times["fused"],
                                   times["super"])

        rec = {"bench": "ingest_throughput", "n": n, "chunk": c,
               "superbatch": superbatch, "pool": pool, "rows": rows,
               "log2_cols": log2_cols,
               "twosort_pps": n / t_two,
               "fused_pps": n / t_fused,
               "fused_superbatch_pps": n / t_super,
               "speedup_fused": t_two / t_fused,
               "speedup_fused_superbatch": t_two / t_super}
        records.append(rec)
        print(f"# ingest_throughput N={n:8d} chunk={c:5d} "
              f"twosort={rec['twosort_pps'] / 1e6:6.3f} "
              f"fused={rec['fused_pps'] / 1e6:6.3f} "
              f"fused+superbatch={rec['fused_superbatch_pps'] / 1e6:6.3f} "
              f"Mpts/s  speedup={rec['speedup_fused_superbatch']:.2f}x",
              flush=True)

    return emit_json({"bench": "ingest_throughput",
                      "speedup_at_max_n":
                          records[-1]["speedup_fused_superbatch"],
                      "records": records}, json_out)


def main() -> None:
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sizes", default="65536,262144,1048576")
    ap.add_argument("--chunk", type=int, default=4096)
    ap.add_argument("--superbatch", type=int, default=16)
    ap.add_argument("--bins", type=int, default=16)
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--log2-cols", type=int, default=16)
    ap.add_argument("--top-k", type=int, default=20480)
    ap.add_argument("--pool", type=int, default=0,
                    help="candidate reservoir size L (0 -> 4*top_k, the "
                         "deep churn-regime setting)")
    ap.add_argument("--json-out", default=DEFAULT_JSON)
    args = ap.parse_args()
    sizes = tuple(int(s) for s in args.sizes.split(","))
    print(run(sizes=sizes, chunk=args.chunk, superbatch=args.superbatch,
              bins=args.bins, rows=args.rows, log2_cols=args.log2_cols,
              top_k=args.top_k, pool=args.pool, json_out=args.json_out))


if __name__ == "__main__":
    main()
