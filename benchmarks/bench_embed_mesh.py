"""Mesh-parallel embed stage: device-count scaling for both embedders.

The PR-6 tentpole claim: the whole sparse embed iteration runs inside
``shard_map`` on a 1-D embed mesh (row-block state + contiguous edge
slices, one all_gather + fixed-size psums per step, no cross-device
scatter), so adding devices divides the per-device edge/row work.  This
bench measures it directly:

* ``tsne``  — optimizer iters/sec of the jitted sharded stage
  (``tsne._sparse_stage_mesh``: kNN attraction + psum'd CIC/FFT repulsion
  + sharded momentum update), setup excluded;
* ``umap``  — epochs/sec of the jitted sharded SGD loop
  (``umap._optimize_embedding_mesh``), setup excluded;
* at 1 device the plain single-device drivers (``_sparse_stage`` /
  ``_optimize_embedding_jit``) run too, so the shard_map overhead at
  D=1 is visible next to the true baseline.

On an accelerator every device count runs in THIS process, each mesh
built from ``jax.devices()[:D]``: a chip belongs to one process, so a
child started after the parent touched JAX could not have it.  Device
counts above what the host has are reported as skipped.  On the CPU each
device count runs in its OWN subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=D`` (the flag must be
set before jax initializes, and the parent process keeps its 1-device
view).  Virtual host devices share the machine's cores, so CPU numbers
show overhead trends and collective counts more than true speedup — on a
real multi-chip mesh the same jaxpr is what runs.

    PYTHONPATH=src python -m benchmarks.bench_embed_mesh \
        --devices 1,2,4,8 --n 20000 --json-out BENCH_embed_mesh.json

Emits a JSON trajectory (default: BENCH_embed_mesh.json at the repo
root, the tracked baseline); ``run()`` returns it as a string for
benchmarks/run.py.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import Optional, Sequence

MARKER = "@@EMBED_MESH@@ "
DIMS = 8


def _measure(devices: int, n: int, knn: int, grid: int, tsne_iters: int,
             umap_epochs: int) -> dict:
    """Time both sharded embedders on ``jax.devices()[:devices]`` in this
    process; returns the record."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import time_fn
    from repro.core import coo, tsne, umap
    from repro.core import mesh as mesh_mod

    assert jax.device_count() >= devices, \
        f"{jax.device_count()} devices visible, wanted {devices}"
    rng = np.random.default_rng(0)
    x = jnp.asarray(np.concatenate([
        rng.normal(0, 1, (n // 2, DIMS)),
        rng.normal(6, 1, (n - n // 2, DIMS))]).astype(np.float32))
    mesh = mesh_mod.make_embed_mesh(devices)
    rec = {"devices": devices, "n": n, "knn": knn, "grid": grid}

    # ---- tSNE: time the jitted sharded stage, setup excluded
    tc = tsne.TsneConfig(backend="sparse", knn=knn, grid_size=grid,
                         n_iter=tsne_iters)
    sp = tsne._sparse_setup_p_mesh(x, None, cfg=tc, mesh=mesh)
    ssp = tsne.shard_sparse_p(sp, n, devices)
    _, n_pad = mesh_mod.row_block(n, devices)
    y0 = 1e-4 * jax.random.normal(jax.random.key(0), (n_pad, 2))
    state = tsne.TsneState(y0, jnp.zeros_like(y0), jnp.ones_like(y0))
    kls = jnp.zeros((tsne_iters,))
    it0 = jnp.asarray(0, jnp.int32)
    stage = functools.partial(tsne._sparse_stage_mesh, cfg=tc,
                              count=tsne_iters, grid_size=grid,
                              mesh=mesh, n=n)
    rec["tsne_iters_per_sec"] = tsne_iters / time_fn(stage, state, kls,
                                                     ssp, it0)

    # ---- UMAP: time the jitted sharded epoch loop, setup excluded
    uc = umap.UmapConfig(n_epochs=umap_epochs, n_neighbors=min(knn, 15),
                         block=4096)
    idx, dist = umap.knn_graph(x, uc.n_neighbors, block=uc.block, mesh=mesh)
    edges, memb = umap.fuzzy_simplicial_set(idx, dist)
    layout, order = coo.edge_layout(edges[:, 0], edges[:, 1], n)
    memb_n = (memb / jnp.maximum(jnp.max(memb), 1e-12))[order]
    slay = coo.shard_edge_layout(np.asarray(layout.src),
                                 np.asarray(layout.dst), n, devices)
    memb_s = coo.shard_payload(slay, memb_n)
    opt = functools.partial(umap._optimize_embedding_mesh, cfg=uc, n=n,
                            e_total=int(layout.src.shape[0]), mesh=mesh)
    rec["umap_epochs_per_sec"] = umap_epochs / time_fn(
        opt, jax.random.key(1), slay, memb_s, None)

    if devices == 1:
        # the true single-device baselines, same sizes
        sstage = functools.partial(tsne._sparse_stage, cfg=tc,
                                   count=tsne_iters, grid_size=grid)
        s0 = tsne.TsneState(y0[:n], jnp.zeros((n, 2)), jnp.ones((n, 2)))
        rec["tsne_single_iters_per_sec"] = tsne_iters / time_fn(
            sstage, s0, kls, sp, it0)
        rec["umap_single_epochs_per_sec"] = umap_epochs / time_fn(
            functools.partial(umap._optimize_embedding_jit, n=n, cfg=uc),
            jax.random.key(1), edges, memb)

    return rec


def _in_subprocess(d: int, n: int, knn: int, grid: int, tsne_iters: int,
                   umap_epochs: int) -> dict:
    """CPU only: measure D virtual host devices in a child process whose
    XLA_FLAGS are set before it imports jax."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={d} "
        + env.get("XLA_FLAGS", "")).strip()
    cmd = [sys.executable, "-m", "benchmarks.bench_embed_mesh",
           "--worker", str(d), "--n", str(n), "--knn", str(knn),
           "--grid", str(grid), "--tsne-iters", str(tsne_iters),
           "--umap-epochs", str(umap_epochs)]
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=3600)
    if out.returncode != 0:
        raise RuntimeError(
            f"embed_mesh worker (D={d}) failed:\n{out.stdout}\n"
            f"{out.stderr}")
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith(MARKER)][-1]
    return json.loads(line[len(MARKER):])


DEFAULT_JSON = None  # resolved lazily: benchmarks.common imports jax


def run(devices: Sequence[int] = (1, 2, 4, 8), n: int = 20_000,
        knn: int = 32, grid: int = 128, tsne_iters: int = 20,
        umap_epochs: int = 20,
        json_out: Optional[str] = "__default__") -> str:
    from benchmarks.common import Csv, repo_root_json
    if json_out == "__default__":
        json_out = repo_root_json("BENCH_embed_mesh.json")
    import jax

    from repro.kernels.registry import ACCELERATOR_BACKENDS
    in_process = jax.default_backend() in ACCELERATOR_BACKENDS
    records = []
    for d in devices:
        if in_process and d > jax.device_count():
            print(f"# embed_mesh D={d} skipped: {jax.device_count()} "
                  f"device(s) on this host", flush=True)
            continue
        measure = _measure if in_process else _in_subprocess
        rec = measure(d, n, knn, grid, tsne_iters, umap_epochs)
        records.append(rec)
        print(f"# embed_mesh D={d} "
              f"tsne={rec['tsne_iters_per_sec']:7.2f} it/s "
              f"umap={rec['umap_epochs_per_sec']:7.2f} ep/s", flush=True)

    csv = Csv(["devices", "tsne_iters_per_sec", "umap_epochs_per_sec"])
    for rec in records:
        csv.add(rec["devices"], f"{rec['tsne_iters_per_sec']:.3f}",
                f"{rec['umap_epochs_per_sec']:.3f}")
    base = records[0]
    summary = {
        "bench": "embed_mesh", "n": n, "knn": knn, "grid": grid,
        "tsne_speedup_at_max_d":
            records[-1]["tsne_iters_per_sec"] / base["tsne_iters_per_sec"],
        "umap_speedup_at_max_d":
            records[-1]["umap_epochs_per_sec"] / base["umap_epochs_per_sec"],
        "records": records}
    from benchmarks.common import emit_json
    emit_json(summary, json_out)
    return csv.dump("embed_mesh — sharded embed stage, device-count scaling "
                    "(virtual CPU devices share cores; see module docstring)")


def main() -> None:
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--worker", type=int, default=0,
                    help="internal: run the measurement in THIS process "
                         "for the given device count")
    ap.add_argument("--devices", default="1,2,4,8")
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--knn", type=int, default=32)
    ap.add_argument("--grid", type=int, default=128)
    ap.add_argument("--tsne-iters", type=int, default=20)
    ap.add_argument("--umap-epochs", type=int, default=20)
    ap.add_argument("--json-out", default="__default__")
    args = ap.parse_args()
    if args.worker:
        rec = _measure(args.worker, args.n, args.knn, args.grid,
                       args.tsne_iters, args.umap_epochs)
        print(MARKER + json.dumps(rec), flush=True)
        return
    devices = tuple(int(s) for s in args.devices.split(","))
    print(run(devices=devices, n=args.n, knn=args.knn, grid=args.grid,
              tsne_iters=args.tsne_iters, umap_epochs=args.umap_epochs,
              json_out=args.json_out))


if __name__ == "__main__":
    main()
