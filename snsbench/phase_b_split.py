#!/usr/bin/env python3
"""Where the million-representative embed spends its time (not a cell).

    python3 snsbench/phase_b_split.py [--reps 1000000] [--seed 0]

Runs the pieces of ``pipeline.embed_points`` for ``configs.sns_paper.
CANCER_1M`` one by one on 10^6 seeded 8-D representatives (the inputs of
``chip_smoke.py``'s phase B): the approximate kNN graph, the perplexity
calibration on it, the assembly of the symmetrized P, and two stages of
10 sparse tSNE iterations at G = 256 (the first compiles).  Each piece
ends in ``block_until_ready``; its wall seconds are split into compile
and run with the benchmark's compile meter.  Prints one JSON object.
Exits 2 without a TPU.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from snsbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=1_000_000)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        jax, _ = harness.start_jax(1, require_tpu=True)
    except harness.NoChip as e:
        print(f"phase_b_split: refused: {e}", file=sys.stderr)
        return 2
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import sns_paper
    from repro.core import neighbors, pipeline, tsne
    from repro.data.synthetic import MixtureSpec, gaussian_mixture
    from snsbench.compile_meter import CompileMeter

    meter = CompileMeter(jax)
    cfg = dataclasses.replace(sns_paper.CANCER_1M, seed=args.seed)
    n = args.reps
    tcfg = tsne.TsneConfig(dims=2, n_iter=20, adaptive_interval=10)
    tcfg = dataclasses.replace(tcfg, learning_rate=n / tcfg.early_exaggeration)
    ecfg = pipeline.resolve_embed_cfg(cfg, tsne_cfg=tcfg)
    k = min(ecfg.knn or max(8, int(round(3.0 * ecfg.perplexity))), n - 1)
    x, _ = gaussian_mixture(n, MixtureSpec(dims=8), seed=args.seed + 3)
    w = np.random.default_rng(args.seed + 4).integers(1, 64, n)
    x, w = jnp.asarray(x), jnp.asarray(w.astype(np.float32))
    out = {"reps": n, "k": k, "device": jax.devices()[0].device_kind}

    def timed(name, fn):
        s0, c0 = meter.snapshot()
        t0 = time.perf_counter()
        r = jax.block_until_ready(fn())
        wall = time.perf_counter() - t0
        s1, c1 = meter.snapshot()
        out[name] = {"wall_s": wall, "compile_s": s1 - s0,
                     "compiles": c1 - c0}
        print(f"{name}: {out[name]}", file=sys.stderr, flush=True)
        return r

    idx, dist = timed("knn_ann", lambda: neighbors.knn_graph(
        x, k, block=ecfg.block, method=ecfg.knn_method, ann=ecfg.ann))
    timed("calibrate", lambda: tsne.calibrate_stats_knn(
        dist, ecfg.perplexity, weights=w,
        search_iters=ecfg.sigma_search_iters))
    sp = timed("p_assembly", lambda: tsne.sparse_p_from_knn(
        idx, dist, ecfg.perplexity, weights=w,
        search_iters=ecfg.sigma_search_iters))
    out["edges"] = int(sp.src.shape[0])
    y0 = 1e-4 * jax.random.normal(jax.random.key(args.seed + 5), (n, 2))
    state = tsne.TsneState(y=y0, velocity=jnp.zeros_like(y0),
                           gains=jnp.ones_like(y0))
    kls = jnp.zeros((tcfg.n_iter,))
    for i, name in enumerate(("stage_1", "stage_2")):
        state, kls = timed(name, lambda: tsne._sparse_stage(
            state, kls, sp, jnp.asarray(10 * i, jnp.int32), cfg=ecfg,
            count=10, grid_size=ecfg.grid_size))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
