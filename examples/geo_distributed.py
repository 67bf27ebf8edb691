"""Geo-distributed Sketch-and-Scale: the full pipeline on a device mesh.

    PYTHONPATH=src python examples/geo_distributed.py

Simulates 2 "data centers" x D/2 edge workers over the D local devices
(8 virtual host devices on the CPU, the chips of an accelerator host).
The whole paper pipeline runs without leaving ``shard_map``:

  ingest — each worker sketches ONLY its local shard on the ("pod",
    "data") mesh; raw points never cross the pod axis, the fixed-size
    sketches merge hierarchically (psum over "data" = intra-DC ICI, then
    "pod" = inter-DC WAN) and every site recovers the identical global
    heavy-hitter list (``core.geo``);
  embed — the weighted heavy-hitter representatives are embedded with the
    optimizer row-block-sharded over a 1-D embed mesh of the same D
    devices (``SnsConfig.embed_mesh`` → ``core.tsne``/``core.umap`` under
    ``shard_map``): per iteration one all_gather of the block positions +
    psums of fixed-size partials, no cross-device scatter.

Mesh/axis plumbing both stages share lives in ``core.mesh``.
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import sys                                                     # noqa: E402
sys.path.insert(0, "src")

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
import numpy as np                                             # noqa: E402

from repro.core import geo, pipeline, quantize                 # noqa: E402
from repro.core import mesh as mesh_mod                        # noqa: E402
from repro.data.synthetic import (MixtureSpec,                 # noqa: E402
                                  clustered_points_sharded)


def main():
    from repro import compile_cache
    compile_cache.enable()
    n_dev = jax.device_count()
    pods = 2 if n_dev % 2 == 0 else 1
    mesh = jax.make_mesh((pods, n_dev // pods), ("pod", "data"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    print(f"[mesh] {dict(mesh.shape)} — pod=data centers, data=edge workers")

    spec = MixtureSpec(dims=6, n_clusters=10, cluster_std=0.015,
                       background_frac=0.3)
    n_per = 50_000
    shards = [clustered_points_sharded(w, n_per, spec, seed=1)
              for w in range(n_dev)]
    pts = jnp.asarray(np.concatenate(shards))
    print(f"[data] {n_dev} x {n_per} points, one shard per worker "
          f"(same underlying mixture, disjoint draws)")

    # ---- ingest → HH: every site must agree on the grid (fixed box, no
    # data pass); sketching + hierarchical merge run inside shard_map
    grid = quantize.GridSpec(dims=spec.dims, bins=16,
                             lo=tuple([0.0] * spec.dims),
                             hi=tuple([1.0] * spec.dims))
    res = geo.geo_extract(mesh, grid, pts, rows=8, log2_cols=14,
                          top_k=256, data_axes=("data", "pod"), seed=0)
    live = int(np.asarray(res.hh.mask).sum())
    cov = float(np.asarray(res.hh.count).sum()) / (n_dev * n_per)
    print(f"[merge] sketch bytes per site = "
          f"{res.merged.table.size * 4 / 2**20:.1f} MiB "
          f"(vs {n_dev * n_per * spec.dims * 4 / 2**20:.0f} MiB raw)")
    print(f"[hh] {live} global heavy hitters, coverage {cov:.1%}; "
          f"identical list on every device (replicated output)")

    # show the top-5 cells in data space
    coords = quantize.unpack(grid, (res.hh.key_hi, res.hh.key_lo))
    centers = np.asarray(quantize.cell_center(grid, coords))[:5]
    counts = np.asarray(res.hh.count)[:5]
    for c, n in zip(centers, counts):
        print(f"   cell@{np.round(c, 2).tolist()}  count={n:.0f}")

    # ---- embed: the same devices re-form as a 1-D embed mesh and the
    # UMAP epoch loop runs row-block-sharded under shard_map
    embed_mesh = mesh_mod.make_embed_mesh(n_dev)
    cfg = pipeline.SnsConfig(top_k=256, embedder="umap", embed_block=512,
                             max_replicas=1, embed_mesh=embed_mesh)
    reps, emb, w, _ = pipeline.embed_stage(cfg, grid, res.hh)
    print(f"[embed] {emb.shape[0]} weighted representatives → "
          f"{emb.shape[1]}D, optimizer row-block-sharded over "
          f"{mesh_mod.axis_size(embed_mesh, mesh_mod.EMBED_AXIS)} devices "
          f"('{mesh_mod.EMBED_AXIS}' axis)")
    print(f"[embed] span x={float(emb[:, 0].min()):+.2f}"
          f"..{float(emb[:, 0].max()):+.2f} "
          f"y={float(emb[:, 1].min()):+.2f}..{float(emb[:, 1].max()):+.2f}, "
          f"total weight {float(np.sum(w)):.0f}")


if __name__ == "__main__":
    main()
