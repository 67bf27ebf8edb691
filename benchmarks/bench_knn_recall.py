"""Approximate vs exact kNN build: recall and wall-clock (ROADMAP item 1).

    PYTHONPATH=src python -m benchmarks.bench_knn_recall \
        --sizes 16384,65536,262144 --json-out BENCH_knn_recall.json

Per N (gaussian-mixture blobs, the pipeline's representative geometry):
build the exact graph (``neighbors.knn_graph(method="exact")``, blocked)
and the approximate one (``method="ann"``: multi-probe sketch bucketing +
NN-descent, ``core.ann``), then report

  * recall — fraction of true kNN edges the ann graph recovers,
  * build wall-clock for both and the ann speedup.

The tracked baseline (BENCH_knn_recall.json at the repo root, the
BENCH_*.json convention) is the contract behind switching ``"auto"`` to
the ann path above ``AnnConfig.auto_threshold``: recall ≥ 0.9 with the
build no longer the wall at representative counts.

``--smoke`` runs one small size and **asserts** recall ≥ 0.9 — the CI
recall gate (writes BENCH_knn_recall_ci.json so the tracked full-size
baseline is never clobbered by a CI box).
"""
from __future__ import annotations

import argparse
import time
from typing import Optional, Sequence

import jax
import numpy as np

from benchmarks.common import Csv, emit_json, repo_root_json
from repro.core import neighbors
from repro.core.ann import AnnConfig
from repro.data.synthetic import MixtureSpec, gaussian_mixture

DEFAULT_JSON = repo_root_json("BENCH_knn_recall.json")
SMOKE_RECALL_FLOOR = 0.9


def _blobs(n: int, dims: int, seed: int = 0):
    spec = MixtureSpec(dims=dims, n_clusters=10, cluster_std=0.05,
                       background_frac=0.2)
    pts, _ = gaussian_mixture(n, spec, seed=seed)
    return jax.numpy.asarray(pts)


def recall_vs_exact(ann_idx: np.ndarray, exact_idx: np.ndarray) -> float:
    """Fraction of true kNN edges present in the ann graph (order-free)."""
    n, k = exact_idx.shape
    rows = np.arange(n, dtype=np.int64)[:, None]
    exact_keys = exact_idx.astype(np.int64) + rows * n
    ann_keys = ann_idx.astype(np.int64) + rows * n
    return float(np.isin(ann_keys, exact_keys).mean())


def _timed_build(x, k: int, reps: int, **kw):
    """Median build seconds over ``reps`` post-compile runs + the result
    of the first (compile excluded: one warmup build)."""
    idx, dist = jax.block_until_ready(
        neighbors.knn_graph(x, k, **kw))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(neighbors.knn_graph(x, k, **kw))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2], np.asarray(idx)


def run(sizes: Sequence[int] = (16384, 65536, 262144), k: int = 90,
        dims: int = 8, block: int = 512, ann: Optional[AnnConfig] = None,
        exact_max: int = 262144,
        json_out: Optional[str] = DEFAULT_JSON) -> str:
    """Recall + build-time trajectory; returns the CSV block."""
    cfg = ann if ann is not None else AnnConfig()
    records = []
    csv = Csv(["n", "k", "recall", "t_exact_s", "t_ann_s", "speedup"])
    for n in sizes:
        x = _blobs(n, dims)
        reps = 1 if n >= 131072 else 3
        t_ann, ann_idx = _timed_build(x, k, reps, method="ann", ann=cfg)
        rec = {"n": n, "k": min(k, n - 1), "dims": dims,
               "t_ann_build_s": t_ann,
               "ann": {"probes": cfg.probes, "bucket": cfg.bucket,
                       "iters": cfg.iters, "sample": cfg.sample,
                       "delta": cfg.delta, "tile": cfg.tile}}
        if n <= exact_max:
            t_exact, exact_idx = _timed_build(x, k, reps, method="exact",
                                              block=block)
            rec["t_exact_build_s"] = t_exact
            rec["recall"] = recall_vs_exact(ann_idx, exact_idx)
            rec["speedup_ann_vs_exact"] = t_exact / t_ann
            csv.add(n, rec["k"], f"{rec['recall']:.4f}", f"{t_exact:.2f}",
                    f"{t_ann:.2f}", f"{rec['speedup_ann_vs_exact']:.1f}")
        else:
            csv.add(n, rec["k"], "-", "-", f"{t_ann:.2f}", "-")
        records.append(rec)
        print(f"# knn_recall N={n:7d} k={rec['k']} "
              f"ann={t_ann:.2f}s "
              + (f"exact={rec['t_exact_build_s']:.2f}s "
                 f"recall={rec['recall']:.4f} "
                 f"speedup={rec['speedup_ann_vs_exact']:.1f}x"
                 if "recall" in rec else "(exact skipped)"), flush=True)

    gated = [r for r in records if "recall" in r]
    emit_json({"bench": "knn_recall",
               "recall_at_max_gated_n": gated[-1]["recall"] if gated else
               None,
               "speedup_at_max_gated_n":
                   gated[-1]["speedup_ann_vs_exact"] if gated else None,
               "records": records}, json_out)
    return csv.dump("knn_recall — approximate (sketch bucketing + "
                    "NN-descent) vs exact kNN build")


def run_smoke(n: int = 4096, k: int = 15, dims: int = 8,
              json_out: Optional[str] = "BENCH_knn_recall_ci.json") -> str:
    """CI gate: one small blob set, hard recall assert."""
    out = run(sizes=(n,), k=k, dims=dims, exact_max=n, json_out=json_out)
    import json as json_mod
    with open(json_out) as f:
        rec = json_mod.load(f)["records"][0]
    assert rec["recall"] >= SMOKE_RECALL_FLOOR, (
        f"ann recall {rec['recall']:.4f} < {SMOKE_RECALL_FLOOR} "
        f"at N={n}, k={k}")
    print(f"# smoke OK: recall {rec['recall']:.4f} >= {SMOKE_RECALL_FLOOR}")
    return out


def main() -> None:
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="16384,65536,262144")
    ap.add_argument("--k", type=int, default=90)
    ap.add_argument("--dims", type=int, default=8)
    ap.add_argument("--block", type=int, default=512)
    ap.add_argument("--exact-max", type=int, default=262144)
    ap.add_argument("--json-out", default=DEFAULT_JSON)
    ap.add_argument("--smoke", action="store_true",
                    help="small blob set + hard recall >= 0.9 assert (CI)")
    args = ap.parse_args()
    if args.smoke:
        out = args.json_out if args.json_out != DEFAULT_JSON \
            else "BENCH_knn_recall_ci.json"
        print(run_smoke(json_out=out))
        return
    sizes = tuple(int(s) for s in args.sizes.split(","))
    print(run(sizes=sizes, k=args.k, dims=args.dims, block=args.block,
              exact_max=args.exact_max, json_out=args.json_out))


if __name__ == "__main__":
    main()
