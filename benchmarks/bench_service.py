"""Online SnS service: freshness, warm-vs-cold refresh, transform qps.

    PYTHONPATH=src python -m benchmarks.bench_service \
        --json-out BENCH_service.json

Three serving levers, one scenario (gaussian-mixture stream + a
same-distribution drift batch):

  * freshness  — points/sec ``service.update()`` absorbs into the live
    ingest fold (steady-state, compile excluded), i.e. how fast the
    service tracks a moving stream;
  * warm vs cold — iterations-to-target: run the post-drift re-embed
    once cold and once warm-started from the cached embedding (both with
    the FULL iteration budget), find the first iteration whose KL enters
    the quality band (within ``slack`` = 5% of the cold run's final KL —
    gradient-descent tSNE keeps shaving the fourth decimal for hundreds
    of tail iterations, so a tighter band measures tail-chasing, not
    embedding quality), and report the ratio — the acceptance bar is
    warm ≤ 1/5 of cold;
  * transform  — out-of-sample queries/sec through the batched
    barycentric path at several batch sizes.

``--smoke`` runs a reduced config and **asserts** warm beats cold (the
CI gate; writes BENCH_service_ci.json so the tracked full-size baseline
is never clobbered by a CI box).
"""
from __future__ import annotations

import argparse
import time
from typing import Optional, Sequence

import numpy as np

from benchmarks.common import Csv, emit_json, repo_root_json
from repro.core import pipeline, quantize
from repro.core.service import ServiceConfig, SnsService
from repro.core.tsne import TsneConfig
from repro.data.synthetic import MixtureSpec, gaussian_mixture

DEFAULT_JSON = repo_root_json("BENCH_service.json")
WARM_RATIO_CEIL = 0.2          # acceptance: warm ≤ 1/5 of cold iterations


def _blobs(n: int, dims: int, seed: int):
    spec = MixtureSpec(dims=dims, n_clusters=8, cluster_std=0.05,
                       background_frac=0.1)
    pts, _ = gaussian_mixture(n, spec, seed=seed)
    return np.asarray(pts, np.float32)


def _iters_to_target(trace: np.ndarray, target: float) -> int:
    """First iteration whose KL is ≤ target (1-based count of optimizer
    steps spent).  The trace must reach it — callers derive the target
    from a run that did."""
    hit = np.flatnonzero(trace <= target)
    assert hit.size, f"trace never reached target {target}"
    return int(hit[0]) + 1


def run(n: int = 400_000, drift_frac: float = 0.05, dims: int = 4,
        top_k: int = 1024, n_iter: int = 500, slack: float = 0.05,
        batch_sizes: Sequence[int] = (1024, 16384, 131072),
        transform_iters: int = 3, seed: int = 0,
        json_out: Optional[str] = DEFAULT_JSON,
        assert_ratio: bool = True) -> str:
    base = _blobs(n, dims, seed)
    drift = _blobs(max(1, int(n * drift_frac)), dims, seed + 1)
    cfg = pipeline.SnsConfig(bins=16, rows=8, log2_cols=14, top_k=top_k,
                             ingest_chunk=65_536, embedder="tsne",
                             embed_backend="dense", max_replicas=4,
                             seed=seed)
    tc = TsneConfig(dims=2, n_iter=n_iter, perplexity=30.0)
    # warm_iters = the FULL budget: the warm run must be measured on the
    # same trace length as cold so iterations-to-target is comparable
    scfg = ServiceConfig(warm_iters=n_iter, transform_chunk=4096,
                         transform_k=8)
    grid = quantize.fit_grid(np.concatenate([base, drift]), cfg.bins)
    svc = SnsService(cfg, grid, tsne_cfg=tc, service_cfg=scfg)

    # ---- freshness: first update compiles, second is steady state
    half = n // 2
    first = svc.update(base[:half])
    steady = svc.update(base[half:])

    # ---- serve once (cold), absorb drift, then re-embed both ways on
    # the SAME post-drift heavy-hitter set (refresh() re-extracts
    # deterministically from the state, which it never mutates)
    svc.refresh(mode="cold")
    svc.update(drift)
    warm = svc.refresh(mode="warm")
    cold = svc.refresh(mode="cold")
    cold_trace = np.asarray(cold.kl_trace)
    warm_trace = np.asarray(warm.kl_trace)
    target = float(cold_trace[-1]) * (1.0 + slack)
    cold_iters = _iters_to_target(cold_trace, target)
    warm_iters = _iters_to_target(warm_trace, target)
    ratio = warm_iters / cold_iters

    # ---- transform throughput vs batch size
    n_reps = int(svc._cache.rep_x.shape[0])
    rng = np.random.default_rng(seed + 2)
    transforms = []
    for q in batch_sizes:
        queries = _blobs(int(q), dims, seed + 3)[rng.permutation(int(q))]
        svc.transform(queries[: min(int(q), 4096)])      # compile
        times = []
        for _ in range(transform_iters):
            t0 = time.perf_counter()
            y = svc.transform(queries)                   # returns synced np
            times.append(time.perf_counter() - t0)
        assert np.isfinite(y).all()
        sec = float(np.median(times))
        transforms.append({"batch": int(q), "seconds": sec,
                           "qps": int(q) / sec})

    csv = Csv(["metric", "value", "note"])
    csv.add("ingest_points_per_sec", f"{steady['points_per_sec']:.0f}",
            "steady-state update() absorption")
    csv.add("cold_iters_to_target", cold_iters,
            f"target KL {target:.4f} (cold final +{slack:.0%})")
    csv.add("warm_iters_to_target", warm_iters,
            f"matched {warm.n_matched} reps, {warm.n_new} new")
    csv.add("warm_over_cold", f"{ratio:.3f}",
            f"acceptance ceiling {WARM_RATIO_CEIL}")
    for t in transforms:
        csv.add(f"transform_qps_b{t['batch']}", f"{t['qps']:.0f}",
                f"{t['seconds'] * 1e3:.1f} ms/batch, {n_reps} reps")

    emit_json({"n": n, "drift_frac": drift_frac, "dims": dims,
               "top_k": top_k, "n_reps": n_reps, "n_iter": n_iter,
               "ingest": {"first_points_per_sec": first["points_per_sec"],
                          "steady_points_per_sec":
                              steady["points_per_sec"]},
               "warm_vs_cold": {"target_kl": target, "slack": slack,
                                "cold_iters_to_target": cold_iters,
                                "warm_iters_to_target": warm_iters,
                                "ratio": ratio,
                                "n_matched": warm.n_matched,
                                "n_new": warm.n_new},
               "transform": transforms}, json_out)
    if assert_ratio:
        assert ratio <= WARM_RATIO_CEIL, (
            f"warm refresh took {warm_iters}/{cold_iters} = {ratio:.3f} "
            f"of cold iterations-to-target (> {WARM_RATIO_CEIL})")
    return csv.dump("service — incremental ingest, warm re-embed, "
                    "out-of-sample transform")


def run_smoke(json_out: Optional[str] = "BENCH_service_ci.json") -> str:
    """CI gate: reduced sizes; hard-asserts warm beats cold and that
    transform qps was recorded at ≥ 2 batch sizes."""
    out = run(n=20_000, drift_frac=0.05, dims=3, top_k=128, n_iter=150,
              slack=0.05, batch_sizes=(256, 4096), transform_iters=2,
              json_out=json_out, assert_ratio=False)
    import json as json_mod
    with open(json_out) as f:
        rec = json_mod.load(f)
    wc = rec["warm_vs_cold"]
    assert wc["warm_iters_to_target"] < wc["cold_iters_to_target"], (
        f"warm refresh ({wc['warm_iters_to_target']} iters) did not beat "
        f"cold ({wc['cold_iters_to_target']})")
    assert len(rec["transform"]) >= 2
    assert all(t["qps"] > 0 for t in rec["transform"])
    print(f"# smoke OK: warm {wc['warm_iters_to_target']} < cold "
          f"{wc['cold_iters_to_target']} iters; "
          f"qps {[int(t['qps']) for t in rec['transform']]}")
    return out


def main() -> None:
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=400_000)
    ap.add_argument("--drift-frac", type=float, default=0.05)
    ap.add_argument("--dims", type=int, default=4)
    ap.add_argument("--top-k", type=int, default=1024)
    ap.add_argument("--n-iter", type=int, default=500)
    ap.add_argument("--batch-sizes", default="1024,16384,131072")
    ap.add_argument("--json-out", default=DEFAULT_JSON)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes + hard warm-beats-cold assert (CI)")
    args = ap.parse_args()
    if args.smoke:
        out = args.json_out if args.json_out != DEFAULT_JSON \
            else "BENCH_service_ci.json"
        print(run_smoke(json_out=out))
        return
    sizes = tuple(int(s) for s in args.batch_sizes.split(","))
    print(run(n=args.n, drift_frac=args.drift_frac, dims=args.dims,
              top_k=args.top_k, n_iter=args.n_iter, batch_sizes=sizes,
              json_out=args.json_out))


if __name__ == "__main__":
    main()
