"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only NAME]

Emits CSV blocks (name, value, paper reference) for:
  * sketch_scaling       — paper Fig. 6 (linear time in stream size)
  * error_vs_rank        — paper §III-2 (CS estimate error by HH rank)
  * hh_vs_sampling       — paper §II-2 (HH beats random subsampling)
  * hh_coverage          — paper §IV (cumulative HH mass)
  * collision_model      — paper §III-2 (grid-resolution guidance)
  * pipeline_quality     — paper §IV-1 (contingency-table analog)
  * kernel_paths         — per-op kernel-tier microbench: every registry
                           op timed compiled vs interpret vs XLA ref
                           (--fast runs the numeric smoke gate)
  * embed_scaling        — dense vs tiled vs sparse embedding memory/time vs N
  * embed_throughput     — tSNE gradient iters/sec (dense/tiled/sparse) +
                           UMAP epochs/sec (scatter baseline vs scatter-free)
  * ingest_scaling       — streaming vs one-shot sketch-stage memory vs N
  * ingest_throughput    — points/sec: two-sort vs fused vs fused+superbatch
  * embed_mesh           — sharded embed stage iters/sec vs device count
                           (in this process on an accelerator; one
                           subprocess per D of virtual CPU devices)
  * knn_recall           — approximate (sketch bucketing + NN-descent) vs
                           exact kNN build: recall + wall-clock
  * service              — online service: ingest absorption points/sec,
                           warm vs cold refresh iterations-to-target,
                           out-of-sample transform queries/sec
  * resilience           — quality under shard loss (coverage, widened
                           error bound, HH recall, KL vs no-loss), retry
                           rescue of transient faults, straggler cutoff

Every bench is registered by module name and imported via importlib at
dispatch time — a registered module that fails to import aborts the run
with the import error (no silent skips), and an unknown ``--only`` name
is an error listing the registry.
"""
from __future__ import annotations

import argparse
import importlib
import sys
import time


def _load(module: str):
    """Import a registered bench module, failing LOUDLY if it is absent
    or broken — a bench silently dropping out of the suite is how
    regressions hide."""
    try:
        return importlib.import_module(f"benchmarks.{module}")
    except ImportError as e:
        raise RuntimeError(
            f"registered bench module benchmarks.{module} failed to "
            f"import: {e}") from e


def build_jobs(fast: bool):
    """The registry: (name, module, runner(mod)) per bench."""
    n_scale = 200_000 if fast else 2_000_000
    n_mid = 100_000 if fast else 1_000_000
    n_small = 60_000 if fast else 300_000
    return [
        ("sketch_scaling", "bench_sketch_scaling", lambda m: m.run()),
        ("error_vs_rank", "bench_error_vs_rank", lambda m: m.run(n_scale)),
        ("hh_vs_sampling", "bench_hh_vs_sampling", lambda m: m.run(n_mid)),
        ("hh_coverage", "bench_coverage", lambda m: m.run(n_scale)),
        ("collision_model", "bench_collision_model", lambda m: m.run()),
        ("pipeline_quality", "bench_pipeline_quality",
         lambda m: m.run(n_small)),
        ("kernel_paths", "bench_kernels", lambda m: (
            m.run(smoke=True, json_out="BENCH_kernels_ci.json") if fast
            else m.run(json_out=m.DEFAULT_JSON))),
        ("embed_scaling", "bench_embed_scaling", lambda m: m.run(
            sizes=(4096, 8192) if fast else (8192, 16384, 32768, 65536),
            dense_max=8192 if fast else 16384,
            iters=1 if fast else 2,
            # fast mode must not clobber the tracked full-size baseline
            json_out=None if fast else m.DEFAULT_JSON)),
        ("embed_throughput", "bench_embed_throughput", lambda m: m.run(
            sizes=(4096, 8192) if fast else (16384, 65536, 262144),
            knn=16 if fast else 90,
            grid=64 if fast else 128,
            dense_max=4096 if fast else 16384,
            tiled_max=8192 if fast else 65536,
            iters=2 if fast else 3,
            # k=15 is the UMAP acceptance geometry (paper n_neighbors)
            umap_knn=15, neg_rate=5,
            json_out=None if fast else m.DEFAULT_JSON)),
        ("ingest_scaling", "bench_ingest_scaling", lambda m: m.run(
            sizes=(8192, 32768) if fast
            else (8192, 65536, 262144, 1048576),
            chunk=4096 if fast else 8192,
            oneshot_time_max=32768 if fast else 262144)),
        ("embed_mesh", "bench_embed_mesh", lambda m: m.run(
            devices=(1, 2) if fast else (1, 2, 4, 8),
            n=4096 if fast else 20_000,
            knn=16 if fast else 32,
            grid=64 if fast else 128,
            tsne_iters=5 if fast else 20,
            umap_epochs=5 if fast else 20,
            # fast mode must not clobber the tracked full-size baseline
            json_out=None if fast else "__default__")),
        ("ingest_throughput", "bench_ingest_throughput", lambda m: m.run(
            sizes=(16384, 65536) if fast else (65536, 262144, 1048576),
            chunk=2048 if fast else 4096,
            top_k=2048 if fast else 20480,
            # fast mode must not clobber the tracked full-size baseline
            json_out=None if fast else m.DEFAULT_JSON)),
        ("knn_recall", "bench_knn_recall", lambda m: (
            m.run_smoke(json_out="BENCH_knn_recall_ci.json") if fast
            else m.run(json_out=m.DEFAULT_JSON))),
        ("service", "bench_service", lambda m: (
            m.run_smoke(json_out="BENCH_service_ci.json") if fast
            else m.run(json_out=m.DEFAULT_JSON))),
        ("resilience", "bench_resilience", lambda m: (
            m.run_smoke(json_out="BENCH_resilience_ci.json") if fast
            else m.run(json_out=m.DEFAULT_JSON))),
    ]


def main() -> None:
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced sizes (CI-friendly)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of each bench's "
                         "timed region into DIR/<bench> (opt-in; "
                         "profiling overhead perturbs timings, so never "
                         "set this for baseline runs)")
    args = ap.parse_args()

    from benchmarks.common import maybe_trace

    jobs = build_jobs(args.fast)
    names = [name for name, _, _ in jobs]
    if args.only is not None and args.only not in names:
        raise SystemExit(
            f"--only {args.only!r} matches no registered bench; "
            f"choose from: {', '.join(names)}")
    for name, module, runner in jobs:
        if args.only and args.only != name:
            continue
        mod = _load(module)
        t0 = time.time()
        try:
            with maybe_trace(args.trace, name):
                print(runner(mod))
            print(f"# [{name} done in {time.time() - t0:.1f}s]\n",
                  flush=True)
        except Exception as e:                               # noqa: BLE001
            print(f"# [{name} FAILED: {type(e).__name__}: {e}]\n",
                  file=sys.stderr, flush=True)
            raise


if __name__ == "__main__":
    main()
