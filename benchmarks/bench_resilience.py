"""Quality under shard loss: what partial aggregation actually costs.

    PYTHONPATH=src python -m benchmarks.bench_resilience \
        --json-out BENCH_resilience.json

The paper's deployment model ships per-site summaries to a master; the
resilience layer (PR 9) lets the master proceed when sites are lost.
This bench quantifies the degradation curve on one scenario
(gaussian-mixture stream split over S equal shards, tSNE embed):

  * lose 0 / 1 / 2 of S shards (deterministic chaos via
    ``faults.FaultPlan``) and record, per loss level: coverage, the
    widened heavy-hitter error bound, mass-weighted HH recall against
    the no-loss run, and the final tSNE KL;
  * a flaky-transport run (every shard fails transiently with p = 0.3)
    showing bounded retries recover FULL coverage — resilience is free
    when faults are transient;
  * straggler cutoff wall-clock: a shard sleeping past the deadline
    must not stall the collection.

``--smoke`` reduces sizes and hard-asserts the CI gate: at 1-of-8
shards lost, coverage == 7/8 exactly, HH recall stays above
``RECALL_FLOOR``, and the final KL is within ``KL_RATIO_CEIL`` of the
no-loss run (writes BENCH_resilience_ci.json so the tracked full-size
baseline is never clobbered by a CI box).
"""
from __future__ import annotations

import argparse
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Csv, emit_json, repo_root_json
from repro.core import geo, pipeline, quantize, replicas
from repro.core.faults import FaultPlan
from repro.core.resilience import RetryPolicy
from repro.core.tsne import TsneConfig
from repro.data.synthetic import MixtureSpec, gaussian_mixture

DEFAULT_JSON = repo_root_json("BENCH_resilience.json")
KL_RATIO_CEIL = 1.5     # 1-of-8 lost: final KL within 50% of no-loss
RECALL_FLOOR = 0.70     # ...and ≥ 70% of the no-loss HH mass retained


def _shards(n: int, n_shards: int, dims: int, seed: int):
    spec = MixtureSpec(dims=dims, n_clusters=8, cluster_std=0.05,
                       background_frac=0.1)
    pts, _ = gaussian_mixture(n, spec, seed=seed)
    pts = np.asarray(pts, np.float32)
    per = n // n_shards
    return {s: [pts[s * per:(s + 1) * per]] for s in range(n_shards)}, per


def _hh_mass(hh):
    """{packed key: count} over the live heavy hitters."""
    m = np.asarray(hh.mask).astype(bool)
    keys = (np.asarray(hh.key_hi, np.uint64)[m] << np.uint64(32)) \
        | np.asarray(hh.key_lo, np.uint64)[m]
    return dict(zip(keys.tolist(), np.asarray(hh.count)[m].tolist()))


def _embed_kl(cfg, grid, hh, tc):
    """Reps → tSNE embed → final KL (the quality scalar the loss levels
    are compared on; same key discipline as pipeline.embed_stage)."""
    krep, kembed = jax.random.split(jax.random.key(cfg.seed + 1))
    reps = replicas.make_representatives(
        krep, grid, hh, scheme=cfg.replica_scheme,
        max_replicas=cfg.max_replicas, jitter_frac=cfg.jitter_frac)
    pts, w, _ = replicas.compact(reps)
    ecfg = pipeline.resolve_embed_cfg(cfg, tsne_cfg=tc)
    emb, trace = pipeline.embed_points(cfg, kembed, jnp.asarray(pts),
                                       jnp.asarray(w), ecfg)
    assert np.isfinite(np.asarray(emb)).all()
    return float(np.asarray(trace)[-1]), int(pts.shape[0])


def run(n: int = 200_000, n_shards: int = 8, dims: int = 4,
        top_k: int = 512, n_iter: int = 300,
        drops: Sequence[int] = (0, 1, 2), seed: int = 0,
        json_out: Optional[str] = DEFAULT_JSON) -> str:
    data, per = _shards(n, n_shards, dims, seed)
    cfg = pipeline.SnsConfig(bins=12, rows=8, log2_cols=13, top_k=top_k,
                             candidate_pool=2 * top_k,
                             ingest_chunk=16_384, embedder="tsne",
                             embed_backend="dense", max_replicas=4,
                             seed=seed)
    tc = TsneConfig(dims=2, n_iter=n_iter, perplexity=20.0)
    grid = quantize.fit_grid(
        np.concatenate([c for v in data.values() for c in v]), cfg.bins)
    expected = {s: float(per) for s in range(n_shards)}
    policy = RetryPolicy(max_attempts=3, base_delay=0.01)

    def extract(faults=None, deadline=None, pol=policy):
        return geo.resilient_extract(
            grid, data, rows=cfg.rows, log2_cols=cfg.log2_cols,
            top_k=cfg.top_k, candidate_pool=cfg.candidate_pool,
            seed=cfg.seed, chunk_size=cfg.ingest_chunk, policy=pol,
            expected_counts=expected, faults=faults, deadline=deadline)

    # ---- degradation curve: lose 0, 1, 2, ... shards
    base_mass = None
    levels = []
    for k in sorted(drops):
        mask = tuple(range(1, 1 + k))        # deterministic victim set
        res = extract(faults=FaultPlan(seed=seed, drop_shards=mask)
                      if mask else None)
        kl, n_reps = _embed_kl(cfg, grid, res.hh, tc)
        mass = _hh_mass(res.hh)
        if base_mass is None:
            base_mass = mass
        total = sum(base_mass.values())
        recall = sum(c for key, c in base_mass.items()
                     if key in mass) / total
        levels.append({"lost_shards": k, "coverage": res.coverage,
                       "hh_error_bound": res.hh_error_bound,
                       "hh_recall_mass": recall, "final_kl": kl,
                       "n_reps": n_reps})

    # ---- transient faults: retries buy back full coverage
    flaky = extract(faults=FaultPlan(seed=seed, flaky=0.3),
                    pol=RetryPolicy(max_attempts=6, base_delay=0.01))
    assert flaky.coverage == 1.0, \
        f"retries failed to rescue flaky shards: {flaky.coverage}"

    # ---- straggler cutoff: a sleeping shard must not stall the merge
    slow = dict(data)

    def sleeper(chunks=data[0]):
        time.sleep(8.0)
        return list(chunks)

    slow[0] = sleeper
    t0 = time.perf_counter()
    strag = geo.resilient_extract(
        grid, slow, rows=cfg.rows, log2_cols=cfg.log2_cols,
        top_k=cfg.top_k, candidate_pool=cfg.candidate_pool,
        seed=cfg.seed, chunk_size=cfg.ingest_chunk,
        policy=RetryPolicy(max_attempts=1), expected_counts=expected,
        deadline=3.0)
    cutoff_s = time.perf_counter() - t0
    assert 0 in strag.lost and cutoff_s < 8.0

    kl0 = levels[0]["final_kl"]
    csv = Csv(["metric", "value", "note"])
    for lv in levels:
        k = lv["lost_shards"]
        csv.add(f"coverage_lost{k}", f"{lv['coverage']:.4f}",
                f"{k}/{n_shards} shards dropped")
        csv.add(f"hh_error_bound_lost{k}", f"{lv['hh_error_bound']:.0f}",
                "survivor watermark + lost mass")
        csv.add(f"hh_recall_lost{k}", f"{lv['hh_recall_mass']:.4f}",
                "mass-weighted vs no-loss HH set")
        csv.add(f"kl_ratio_lost{k}", f"{lv['final_kl'] / kl0:.4f}",
                f"final KL {lv['final_kl']:.4f} vs {kl0:.4f}")
    csv.add("flaky_retries", flaky.retries,
            "p=0.3 transient/attempt, full coverage recovered")
    csv.add("straggler_cutoff_sec", f"{cutoff_s:.2f}",
            "8s sleeper, 3s deadline: merge not stalled")

    emit_json({"bench": "resilience", "n": n, "n_shards": n_shards,
               "per_shard": per, "top_k": top_k, "n_iter": n_iter,
               "levels": levels,
               "flaky": {"p": 0.3, "retries": flaky.retries,
                         "coverage": flaky.coverage},
               "straggler": {"deadline": 3.0,
                             "cutoff_seconds": cutoff_s}}, json_out)
    return csv.dump("resilience — quality under shard loss, retry "
                    "rescue, straggler cutoff")


def run_smoke(json_out: Optional[str] = "BENCH_resilience_ci.json") -> str:
    """CI gate: 1-of-8 shards lost must degrade, not collapse."""
    out = run(n=24_000, n_shards=8, dims=3, top_k=128, n_iter=120,
              drops=(0, 1), json_out=json_out)
    import json as json_mod
    with open(json_out) as f:
        rec = json_mod.load(f)
    by_k = {lv["lost_shards"]: lv for lv in rec["levels"]}
    l0, l1 = by_k[0], by_k[1]
    assert l0["coverage"] == 1.0
    assert abs(l1["coverage"] - 7 / 8) < 1e-9, l1["coverage"]
    assert l1["hh_error_bound"] > l0["hh_error_bound"]
    assert l1["hh_recall_mass"] >= RECALL_FLOOR, l1["hh_recall_mass"]
    ratio = l1["final_kl"] / l0["final_kl"]
    assert ratio <= KL_RATIO_CEIL, (
        f"1-of-8 shard loss blew up the embedding: final KL ratio "
        f"{ratio:.3f} > {KL_RATIO_CEIL}")
    print(f"# smoke OK: coverage {l1['coverage']:.4f}, recall "
          f"{l1['hh_recall_mass']:.3f}, KL ratio {ratio:.3f}")
    return out


def main() -> None:
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--n-shards", type=int, default=8)
    ap.add_argument("--dims", type=int, default=4)
    ap.add_argument("--top-k", type=int, default=512)
    ap.add_argument("--n-iter", type=int, default=300)
    ap.add_argument("--drops", default="0,1,2")
    ap.add_argument("--json-out", default=DEFAULT_JSON)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes + hard degradation asserts (CI)")
    args = ap.parse_args()
    if args.smoke:
        out = args.json_out if args.json_out != DEFAULT_JSON \
            else "BENCH_resilience_ci.json"
        print(run_smoke(json_out=out))
        return
    drops = tuple(int(s) for s in args.drops.split(","))
    print(run(n=args.n, n_shards=args.n_shards, dims=args.dims,
              top_k=args.top_k, n_iter=args.n_iter, drops=drops,
              json_out=args.json_out))


if __name__ == "__main__":
    main()
