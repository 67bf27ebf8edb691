"""Ahead-of-time compiles for a described TPU v5e, at the sizes the main
path reaches on the chip.

Interpret-mode tests cannot see what the TPU compiler refuses: a block
shape off the (8, 128) tiling, a scalar store to VMEM, a working set over
the scoped VMEM limit, a program over the chip's 16 GB.  These tests
compile the compiled tier of every kernel-registry op at the shapes auto
mode or a user option reaches on the chip, plus the sparse tSNE stage at
CANCER_1M size and the UMAP loop at CANCER size, for ``v5e:2x2`` —
nothing runs.

The topology is described inside a module-scoped fixture, never at
import: only the worker that runs this file loads the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import sns_paper
from repro.core import pipeline, tsne, umap
from repro.kernels import registry
from repro.kernels import segment_reduce as segred

HBM_BYTES = 16 * 10**9          # one v5e chip
CANCER_1M_N = 1_000_000
POINTS = 1 << 20                # CIC points at CANCER_1M size (padded)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                               # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off here
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _device_bytes(compiled):
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def _compiled(op):
    return registry.get(op, "compiled").fn


F32, I32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("grid", [256, 1024])
def test_cic_splat_compiles(one_chip, grid):
    block = registry.tile_params("cic_splat", backend="tpu")["block_items"]
    fn = functools.partial(_compiled("cic_splat"), grid_size=grid,
                           block_items=block)
    _compile(fn, one_chip, ((POINTS, 2), I32), ((POINTS, 2), F32),
             ((POINTS, 3), F32))


@pytest.mark.parametrize("grid", [256, 1024])
def test_cic_gather_compiles(one_chip, grid):
    block = registry.tile_params("cic_gather", backend="tpu")["block_items"]
    fn = functools.partial(_compiled("cic_gather"), block_items=block)
    _compile(fn, one_chip, ((4, grid, grid), F32), ((POINTS, 2), I32),
             ((POINTS, 2), F32))


def test_knn_dist_tiles_compiles(one_chip):
    # ann stage 1 at k = 90: 8 tiles per step, B = 128 rows, 3B window
    t, b, d = 8, 128, 8
    _compile(_compiled("knn_dist_tiles"), one_chip, ((t, b, d), F32),
             ((t, b), I32), ((t, 3 * b, d), F32), ((t, 3 * b), I32))


@pytest.mark.parametrize("block", [128, 512])
def test_tsne_step_compiles(one_chip, block):
    """The pallas gradient backend's tile at the TPU table size and at
    a larger requested block (run as 128-row tiles)."""
    n = 8192
    fn = functools.partial(_compiled("tsne_step"), block=block, n_valid=n)
    _compile(fn, one_chip, ((n, 8), F32), ((n, 2), F32), ((n, 4), F32),
             ((), F32))


def test_segment_reduce_compiles_up_to_its_vmem_bound(one_chip):
    """The largest payload the compiled tier accepts compiles; one past
    it is refused by ``supported`` instead of by the TPU compiler."""
    n, width = 4096, 2
    sup = registry.get("segment_reduce", "compiled").supported

    def fits(e):
        return sup("tpu", (n, e, width), F32)

    e = max(e for e in range(512, 1 << 16, 512) if fits(e))
    assert not fits(e + 512)
    tiles = registry.tile_params("segment_reduce", backend="tpu")
    assert segred.vmem_bytes(n, e, width, **tiles) <= segred.VMEM_BUDGET
    fn = functools.partial(_compiled("segment_reduce"), **tiles)
    _compile(fn, one_chip, ((e, width), F32), ((n + 1,), I32))
    with pytest.raises(registry.KernelUnavailableError):
        registry.resolve("segment_reduce", mode="compiled", backend="tpu",
                         shape=(n, 122_880, width))


def test_sparse_tsne_stage_fits_one_chip_at_cancer_1m(one_chip):
    """One adaptive-grid stage of the sparse tSNE optimizer at
    N = 10^6, k = 90 (E = 2Nk = 1.8e8 edges), at the grid cap G = 1024."""
    cfg = pipeline.resolve_embed_cfg(sns_paper.CANCER_1M)
    n = CANCER_1M_N
    e = 2 * n * (cfg.knn or int(3 * cfg.perplexity))

    def stage(y, vel, gains, kls, src, dst, val, bounds, it0):
        return tsne._sparse_stage(
            tsne.TsneState(y, vel, gains), kls,
            tsne.SparseP(src, dst, val, bounds), it0, cfg=cfg, count=1,
            grid_size=cfg.grid_max)

    compiled = _compile(stage, one_chip, ((n, 2), F32), ((n, 2), F32),
                        ((n, 2), F32), ((cfg.n_iter,), F32), ((e,), I32),
                        ((e,), I32), ((e,), F32), ((n + 1,), I32), ((), I32))
    assert _device_bytes(compiled) < HBM_BYTES


def test_umap_epoch_loop_at_cancer_size_stays_small(one_chip):
    """The CANCER refresh's UMAP loop on the largest representative set
    its replica budget allows (20,000 cells x 8).  Per-edge values are
    1-D per coordinate; lane-padded (E, 2)-shaped intermediates took
    4.4 GiB at 60,000 points."""
    cfg = pipeline.resolve_embed_cfg(sns_paper.CANCER)
    n = sns_paper.CANCER.top_k * sns_paper.CANCER.max_replicas
    e = 2 * n * cfg.n_neighbors
    key = jax.random.key(0)

    def loop(k, edges, memb):
        return umap._optimize_embedding_jit(k, edges, memb, n, cfg)

    key_shape = jax.ShapeDtypeStruct(key.shape, key.dtype,
                                     sharding=one_chip)
    edges = jax.ShapeDtypeStruct((e, 2), I32, sharding=one_chip)
    memb = jax.ShapeDtypeStruct((e,), F32, sharding=one_chip)
    compiled = jax.jit(loop).lower(key_shape, edges, memb).compile()
    assert _device_bytes(compiled) < 1 << 30
