"""Dry-run + roofline for the paper's OWN pipeline at pod scale.

Lowers a ``geo_extract``-shaped SPMD program (quantize → pack →
per-device Count Sketch update + local top-L → ``geo.merge_shard``, the
master's merge that ``SnsService`` runs: hierarchical psum of the tables,
counts and watermarks → all-gather candidates → global top-K) on the
production mesh, with a configurable
per-device batch: 512 devices × 2²⁰ points/step ≈ 5.4·10⁸ points per
step — the paper's "billions across data centers" regime is a few such
steps.

    python -m repro.launch.sns_dryrun [--multi-pod] [--rows 16]
        [--log2-cols 18] [--top-k 20000] [--pool 0] [--per-device 1048576]
        [--out results/sns_perf/baseline.json]
"""
import os
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import candidates as cand_mod
from repro.core import geo
from repro.core import quantize, sketch as sketch_mod
from repro.core.quantize import GridSpec
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--log2-cols", type=int, default=18)
    ap.add_argument("--top-k", type=int, default=20_000)
    ap.add_argument("--pool", type=int, default=0,
                    help="candidate pool per shard (0 -> 2*top_k)")
    ap.add_argument("--per-device", type=int, default=1 << 20)
    ap.add_argument("--dims", type=int, default=8)
    ap.add_argument("--bins", type=int, default=25)
    ap.add_argument("--update", choices=("sorted", "scatter"),
                    default="sorted")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    data_axes = tuple(a for a in mesh.axis_names)   # all axes carry data
    n_dev = int(np.prod(list(mesh.shape.values())))
    n_total = n_dev * args.per_device
    pool = args.pool or 2 * args.top_k

    grid = GridSpec(dims=args.dims, bins=args.bins,
                    lo=tuple([0.0] * args.dims), hi=tuple([1.0] * args.dims))
    sk0 = sketch_mod.init(jax.random.key(0), args.rows, args.log2_cols)
    upd = sketch_mod.update_sorted if args.update == "sorted" \
        else sketch_mod.update

    @geo.shard_map_compat(mesh=mesh, in_specs=(P(), P(data_axes)),
                          out_specs=P())
    def spmd(sk, pts):
        key_hi, key_lo = quantize.points_to_keys(grid, pts)
        sk_local = upd(sk, key_hi, key_lo)
        cands, dropped = cand_mod.topk_from_runs(
            cand_mod.sorted_runs(key_hi, key_lo), pool, return_dropped=True)
        return geo.merge_shard(sk_local, cands,
                               jnp.full((), pts.shape[0], jnp.float32),
                               dropped, args.top_k, data_axes)

    pts_spec = jax.ShapeDtypeStruct((n_total, args.dims), jnp.float32)
    sk_spec = jax.eval_shape(lambda: sk0)
    pts_sh = NamedSharding(mesh, P(data_axes))
    sk_sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), sk_spec)

    t0 = time.time()
    lowered = jax.jit(spmd, in_shardings=(sk_sh, pts_sh)).lower(
        sk_spec, pts_spec)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    ana = analyze_hlo(hlo)
    rec = {
        "config": vars(args), "devices": n_dev, "points_per_step": n_total,
        "mesh": "(2,16,16)" if args.multi_pod else "(16,16)",
        "compile_s": round(time.time() - t0, 1),
        "hlo_tripaware": ana,
        "cost": {k: float(v) for k, v in
                 (compiled.cost_analysis() or {}).items()
                 if k in ("flops", "bytes accessed")},
    }
    # roofline terms (per device)
    tc = ana["flops"] / 197e12
    tm = ana["bytes"] / 819e9
    ici = ana["collective_bytes"] - ana["collective_dcn_bytes"]
    tcl = ici / 50e9 + ana["collective_dcn_bytes"] / 25e9
    rec["roofline"] = {
        "compute_ms": round(tc * 1e3, 3), "memory_ms": round(tm * 1e3, 3),
        "collective_ms": round(tcl * 1e3, 3),
        "bottleneck": max([("compute", tc), ("memory", tm),
                           ("collective", tcl)], key=lambda x: x[1])[0],
        "points_per_sec_at_bound": n_total / max(tc, tm, tcl),
    }
    out = json.dumps(rec, indent=1)
    print(out)
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(out)


if __name__ == "__main__":
    main()
