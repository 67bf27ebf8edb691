"""Several sites through SnsService (``SnsConfig.sites``) against plain
references: the exact cell counts of the union of the sites' chunks, and
a NumPy float64 sum of the per-site tables.

The main test process sees one CPU device, so four sites share it there;
the same four sites on four devices run once in a subprocess with
``--xla_force_host_platform_device_count=4`` (the flag must be set before
jax initializes), which saves its states, its merged view and the
collectives of its two programs for the tests below to compare.
"""
import dataclasses
import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import geo, pipeline, quantize, stream
from repro.core.service import SnsService
from repro.core.umap import UmapConfig

SITES = 4
# 6^3 = 216 cells < the 400-slot reservoir: no site ever evicts, so the
# reservoirs hold exact counts of every cell they saw
CFG = pipeline.SnsConfig(bins=6, rows=8, log2_cols=16, top_k=200,
                         ingest_chunk=8192, ingest_superbatch=2,
                         max_replicas=1, sites=SITES, seed=5)
GRID = quantize.GridSpec(dims=3, bins=6, lo=(0.0,) * 3, hi=(1.0,) * 3)
COLLECTIVES = ("psum", "pmax", "pmin", "all_gather", "all_to_all",
               "ppermute", "reduce_scatter")


def site_chunks(site: int, rnd: int) -> np.ndarray:
    """Site ``site``'s points of round ``rnd``: its own clusters over a
    uniform background, ragged (not a whole number of chunks)."""
    rng = np.random.default_rng([site, 1])
    centers = rng.uniform(0.1, 0.9, size=(3, 3))
    rng = np.random.default_rng([site, 2, rnd])
    n = 8192 * 2 + 1000 * (site + 1)
    pts = centers[rng.integers(0, 3, n)] + 0.06 * rng.normal(size=(n, 3))
    bg = rng.random(n) < 0.3
    pts[bg] = rng.random((int(bg.sum()), 3))
    return np.clip(pts, 0.0, 1.0).astype(np.float32)


def fed(rounds: int = 2):
    """``[[round 0, round 1, ...] for each site]``."""
    return [[site_chunks(s, r) for r in range(rounds)] for s in range(SITES)]


def run_service(cfg=CFG):
    """A service of ``cfg.sites`` sites fed every round, and its merge."""
    svc = SnsService(cfg, GRID)
    for r in range(2):
        svc.update([rounds[r] for rounds in fed()])
    return svc, svc.merge()


def collectives(jaxpr) -> list:
    """``[(primitive, [operand shape and dtype, ...])]`` of every
    collective in a jaxpr, recursively."""
    out = []
    for eqn in jaxpr.eqns:
        if any(c in eqn.primitive.name for c in COLLECTIVES):
            out.append((eqn.primitive.name,
                        [f"{v.aval.dtype}{list(v.aval.shape)}"
                         for v in eqn.invars]))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                if hasattr(sub, "eqns"):
                    out += collectives(sub)
                elif hasattr(sub, "jaxpr"):
                    out += collectives(sub.jaxpr)
    return out


def program_collectives(svc) -> dict:
    """The collectives of the service's fold and merge programs."""
    pts = jax.ShapeDtypeStruct((SITES, CFG.ingest_superbatch,
                                CFG.ingest_chunk, 3), jnp.float32)
    mask = jax.ShapeDtypeStruct(pts.shape[:3], jnp.bool_)
    fold = jax.make_jaxpr(functools.partial(
        geo.fold_sites, grid=GRID, mesh=svc.mesh))(svc.state, pts, mask)
    merge = jax.make_jaxpr(functools.partial(
        geo.merge_sites, mesh=svc.mesh, top_k=CFG.top_k))(svc.state)
    return {"fold": collectives(fold.jaxpr),
            "merge": collectives(merge.jaxpr)}


def dump(path: str) -> None:
    """Run the service on every device JAX sees; save what the tests
    compare (the subprocess entry point)."""
    svc, view = run_service()
    leaves = {f"state{i}": np.asarray(x) for i, x in
              enumerate(jax.tree_util.tree_leaves(svc.state))}
    leaves.update({f"view{i}": np.asarray(x) for i, x in
                   enumerate(jax.tree_util.tree_leaves(view))})
    np.savez(path + ".npz", **leaves)
    try:
        geo.site_mesh(6)
        uneven = "accepted"
    except ValueError as e:
        uneven = str(e)
    with open(path + ".json", "w") as f:
        json.dump({"devices": int(svc.mesh.devices.size),
                   "site_devices": [sorted(d.id for d in
                                           s.data.devices())[0]
                                    for s in svc.state.count
                                    .addressable_shards],
                   "uneven": uneven,
                   "collectives": program_collectives(svc)}, f)


@pytest.fixture(scope="module")
def one_device():
    return run_service()


@pytest.fixture(scope="module")
def four_devices(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("geo4") / "four")
    here = os.path.dirname(os.path.abspath(__file__))
    script = (
        "import os, sys\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=4'\n"
        f"sys.path.insert(0, {here!r})\n"
        "import test_geo_service as t\n"
        f"t.dump({path!r})\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(here, "..", "src"), os.path.join(here, "..")])
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:{out.stdout}\nSTDERR:{out.stderr}"
    with open(path + ".json") as f:
        meta = json.load(f)
    return dict(np.load(path + ".npz")), meta


def _keys(hh) -> np.ndarray:
    live = np.asarray(hh.mask).astype(bool)
    return (np.asarray(hh.key_hi, np.uint64)[live] << np.uint64(32)) \
        | np.asarray(hh.key_lo, np.uint64)[live]


def _exact_counts():
    pts = np.concatenate([c for rounds in fed() for c in rounds])
    hi, lo = quantize.points_to_keys(GRID, pts)
    keys = (np.asarray(hi, np.uint64) << np.uint64(32)) | \
        np.asarray(lo, np.uint64)
    return np.unique(keys, return_counts=True)


def test_served_keys_are_the_exact_top_k(one_device):
    svc, view = one_device
    assert float(view.evict_max) == 0.0        # no site evicted
    uniq, counts = _exact_counts()
    assert float(view.total_count) == counts.sum()
    kth = np.sort(counts)[::-1][CFG.top_k - 1]
    served = _keys(view.hh)
    assert served.size == CFG.top_k
    # every cell above the K-th count is served, none below it is
    assert set(uniq[counts > kth].tolist()) <= set(served.tolist())
    exact = dict(zip(uniq.tolist(), counts.tolist()))
    assert min(exact[k] for k in served.tolist()) >= kth
    np.testing.assert_array_equal(
        np.asarray(view.hh.count)[np.asarray(view.hh.mask)],
        [exact[k] for k in served.tolist()])


def test_merged_table_is_the_sum_of_the_site_tables(one_device):
    svc, view = one_device
    merged = np.asarray(view.merged.table)
    sites = np.asarray(svc.state.sketch.table, np.float64)
    assert sites.shape[0] == SITES
    np.testing.assert_array_equal(merged.astype(np.float64),
                                  sites.sum(axis=0))
    # ...and the table of one site fed every site's points
    one = SnsService(dataclasses.replace(CFG, sites=1), GRID)
    one.update([c for rounds in fed() for c in rounds])
    np.testing.assert_array_equal(merged, np.asarray(one.state.sketch.table))
    np.testing.assert_array_equal(
        np.asarray(svc.state.count),
        [sum(c.shape[0] for c in rounds) for rounds in fed()])


def test_one_and_four_devices_are_bit_identical(one_device, four_devices):
    svc, view = one_device
    saved, meta = four_devices
    assert meta["devices"] == SITES
    assert meta["site_devices"] == list(range(SITES))   # a site a device
    for i, x in enumerate(jax.tree_util.tree_leaves(svc.state)):
        np.testing.assert_array_equal(np.asarray(x), saved[f"state{i}"])
    for i, x in enumerate(jax.tree_util.tree_leaves(view)):
        np.testing.assert_array_equal(np.asarray(x), saved[f"view{i}"])


def test_sites_must_divide_the_devices(four_devices):
    _, meta = four_devices
    assert "do not divide evenly" in meta["uneven"]
    with pytest.raises(ValueError, match="sites"):
        pipeline.SnsConfig(sites=0)


def test_fold_has_no_collective_and_merge_ships_summaries(one_device,
                                                          four_devices):
    svc, _ = one_device
    table = f"float32[{CFG.rows}, {1 << CFG.log2_cols}]"
    for found, devices in ((program_collectives(svc), 1),
                           (four_devices[1]["collectives"], SITES)):
        pool = 2 * CFG.top_k * SITES // devices   # this device's sites
        assert found["fold"] == []
        shipped = sorted(s for _, ops in found["merge"] for s in ops)
        # psum: the table, the count, the watermark; all_gather: the
        # reservoirs' four columns — no points leave a site
        assert shipped == sorted(
            [table, "float32[]", "float32[]", f"uint32[{pool}]",
             f"uint32[{pool}]", f"float32[{pool}]", f"bool[{pool}]"]), \
            found["merge"]


def test_refresh_and_transform_read_the_merged_view():
    cfg = dataclasses.replace(CFG, top_k=40)
    svc = SnsService(cfg, GRID, umap_cfg=UmapConfig(n_neighbors=5,
                                                    n_epochs=5))
    svc.update([rounds[0] for rounds in fed(1)])
    res = svc.refresh()
    view = svc.merge()
    assert svc.merge() is view                 # kept until update()
    np.testing.assert_array_equal(
        np.sort(res.hh_ids), np.flatnonzero(np.asarray(view.hh.mask)))
    y = svc.transform(fed(1)[0][0][:64])
    assert y.shape == (64, 2) and np.isfinite(y).all()
    health = svc.health()
    assert health["geo"]["sites"] == SITES
    assert health["geo"]["merges"] == 1
    assert health["points"] == sum(health["geo"]["site_points"])
    svc.update([rounds[0] for rounds in fed(1)])
    assert svc.merge() is not view


def test_one_site_is_the_one_fold():
    cfg = dataclasses.replace(CFG, sites=1)
    svc = SnsService(cfg, GRID)
    chunks = [c for rounds in fed() for c in rounds]
    svc.update(chunks)
    ref = stream.init(jax.random.key(cfg.seed), cfg.rows, cfg.log2_cols,
                      2 * cfg.top_k)
    ref = stream.ingest_all(ref, GRID, chunks, cfg.ingest_chunk,
                            superbatch=cfg.ingest_superbatch)
    assert svc.mesh is None
    for a, b in zip(jax.tree_util.tree_leaves(svc.state),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_watermark_is_summed_over_the_sites():
    """Two sites each evict cell X with a count just under their own
    watermark: X's total exceeds either watermark, and only their sum
    bounds it."""
    grid = quantize.GridSpec(dims=1, bins=4, lo=(0.0,), hi=(1.0,))
    cfg = pipeline.SnsConfig(bins=4, rows=4, log2_cols=8, top_k=1,
                             candidate_pool=1, ingest_chunk=8,
                             ingest_superbatch=1, sites=2)
    x, y, z = 0.1, 0.4, 0.9                   # cells 0, 1, 3
    site_a = np.array([[x]] * 3 + [[y]] * 4, np.float32)
    site_b = np.array([[x]] * 3 + [[z]] * 4, np.float32)
    svc = SnsService(cfg, grid)
    svc.update([site_a, site_b])
    np.testing.assert_array_equal(np.asarray(svc.state.evict_max), [3, 3])
    view = svc.merge()
    assert float(view.evict_max) == 6.0       # the max would say 3
    assert svc.error_bound() >= 6.0            # X's total count
    served = _keys(view.hh).tolist()
    assert 0 not in served


def test_many_sites_refuse_one_fold_operations(one_device, tmp_path):
    svc, _ = one_device
    with pytest.raises(NotImplementedError, match="sites"):
        svc.update_shards({0: [site_chunks(0, 0)]})
    with pytest.raises(NotImplementedError, match="sites"):
        svc.save(tmp_path / "ckpt")
    with pytest.raises(NotImplementedError, match="sites"):
        SnsService.load(tmp_path / "ckpt", CFG, GRID)
    with pytest.raises(ValueError, match="one chunk source per site"):
        svc.update([site_chunks(0, 0)])
