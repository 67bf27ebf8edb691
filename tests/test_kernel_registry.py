"""Kernel dispatch registry: resolution order, mode precedence, per-op
mode equivalence (padding tails, oversized tiles, dtype promotion), the
zero-Pallas jaxpr pin for forced-XLA paths, and the fused segment-reduce
bit-for-bit contract against the cumsum path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ann as ann_mod
from repro.core import coo, pipeline
from repro.core import tsne as tsne_mod
from repro.core import umap as umap_mod
from repro.kernels import knn_tile, ops, registry
from repro.kernels import segment_reduce as segred

ON_CPU = jax.default_backend() not in registry.ACCELERATOR_BACKENDS

# every mode this backend can actually execute (compiled needs Mosaic)
RUNNABLE = ("interpret", "xla") if ON_CPU \
    else ("compiled", "interpret", "xla")


@pytest.fixture(autouse=True)
def _neutral_mode_env(monkeypatch):
    """These tests probe the precedence chain itself, so the ambient
    CI-matrix pin (SNS_KERNEL_MODE) must not leak in; tests that need
    the env var set it explicitly via monkeypatch."""
    monkeypatch.delenv(registry.ENV_VAR, raising=False)


@pytest.fixture
def fake_op():
    """Install a throwaway op; clean the registry afterwards."""
    name = "_test_probe_op"

    def install(mode, fn=None, **kw):
        return registry.register(name, mode, **kw)(fn or (lambda: mode))

    yield name, install
    registry._REGISTRY.pop(name, None)
    registry.set_mode_override(None, name)
    registry.set_mode_override(None, "*")


# ------------------------------------------------------------ resolution
class TestResolutionOrder:
    def test_auto_walks_compiled_interpret_xla(self, fake_op):
        name, install = fake_op
        install("compiled")
        install("interpret")
        install("xla")
        # on CPU compiled's default accel_only gate declines -> interpret
        got = registry.resolve(name, backend="cpu")
        assert got.mode == "interpret"
        # on an accelerator compiled wins
        got = registry.resolve(name, backend="tpu")
        assert got.mode == "compiled"

    def test_prefer_declines_without_blocking_forced(self, fake_op):
        name, install = fake_op
        install("interpret", prefer=registry.accel_only)
        install("xla")
        # auto on CPU: interpret's prefer declines -> xla
        assert registry.resolve(name, backend="cpu").mode == "xla"
        # but FORCING interpret still works (supported=always)
        assert registry.resolve(name, mode="interpret",
                                backend="cpu").mode == "interpret"

    def test_forced_unsupported_raises_not_downgrades(self, fake_op):
        name, install = fake_op
        install("compiled")
        install("xla")
        with pytest.raises(registry.KernelUnavailableError):
            registry.resolve(name, mode="compiled", backend="cpu")

    def test_forced_unregistered_mode_raises(self, fake_op):
        name, install = fake_op
        install("xla")
        with pytest.raises(registry.KernelUnavailableError):
            registry.resolve(name, mode="interpret", backend="cpu")

    def test_unknown_op_raises(self):
        with pytest.raises(KeyError):
            registry.resolve("_no_such_op_")

    def test_no_impl_accepts_backend_raises(self, fake_op):
        name, install = fake_op
        install("compiled")          # accel_only, nothing else registered
        with pytest.raises(registry.KernelUnavailableError):
            registry.resolve(name, backend="cpu")


class TestAcceleratorAuto:
    """On an accelerator, auto mode never lands on the Pallas
    interpreter: a compiled tier that declines falls through to XLA."""

    def test_declined_compiled_falls_through_to_xla(self, fake_op):
        name, install = fake_op
        install("compiled", supported=lambda b, s, d: False)
        install("interpret")
        install("xla")
        assert registry.resolve(name, backend="tpu").mode == "xla"
        assert registry.resolve(name, backend="gpu").mode == "xla"
        # the interpreter stays the CPU's auto pick and stays forceable
        assert registry.resolve(name, backend="cpu").mode == "interpret"
        assert registry.resolve(name, mode="interpret",
                                backend="tpu").mode == "interpret"

    def test_interpret_only_op_has_no_auto_pick_on_tpu(self, fake_op):
        name, install = fake_op
        install("interpret")
        with pytest.raises(registry.KernelUnavailableError):
            registry.resolve(name, backend="tpu")

    @pytest.mark.parametrize("op", ["cic_splat", "cic_gather",
                                    "knn_dist_tiles", "tsne_step",
                                    "segment_reduce"])
    def test_builtin_ops_never_auto_interpret_on_tpu(self, op):
        assert registry.resolve(op, backend="tpu").mode != "interpret"

    @pytest.mark.parametrize("backend", ["cpu", "tpu", "gpu"])
    def test_segment_reduce_auto_is_the_cumsum_path(self, backend):
        shape = (4096, 122_880, 2)
        assert registry.resolve("segment_reduce", backend=backend,
                                shape=shape).mode == "xla"

    def test_segment_reduce_compiled_refuses_shapes_over_vmem(self):
        with pytest.raises(registry.KernelUnavailableError,
                           match="segment_reduce"):
            registry.resolve("segment_reduce", mode="compiled",
                             backend="tpu", shape=(4096, 122_880, 2))
        assert registry.resolve("segment_reduce", mode="compiled",
                                backend="tpu",
                                shape=(1024, 16_384, 2)).mode == "compiled"


class TestModePrecedence:
    def test_explicit_beats_override_and_env(self, fake_op, monkeypatch):
        name, install = fake_op
        install("interpret")
        install("xla")
        monkeypatch.setenv(registry.ENV_VAR, "interpret")
        registry.set_mode_override("interpret", name)
        assert registry.resolve(name, mode="xla",
                                backend="cpu").mode == "xla"

    def test_override_beats_env(self, fake_op, monkeypatch):
        name, install = fake_op
        install("interpret")
        install("xla")
        monkeypatch.setenv(registry.ENV_VAR, "interpret")
        registry.set_mode_override("xla", name)
        assert registry.resolve(name, backend="cpu").mode == "xla"

    def test_env_beats_auto(self, fake_op, monkeypatch):
        name, install = fake_op
        install("interpret")
        install("xla")
        monkeypatch.setenv(registry.ENV_VAR, "xla")
        assert registry.resolve(name, backend="cpu").mode == "xla"

    def test_global_override_applies_to_all_ops(self, fake_op):
        name, install = fake_op
        install("interpret")
        install("xla")
        registry.set_mode_override("xla", "*")
        try:
            assert registry.resolve(name, backend="cpu").mode == "xla"
        finally:
            registry.set_mode_override(None, "*")

    def test_invalid_mode_strings_raise(self, monkeypatch):
        with pytest.raises(ValueError):
            registry.resolve_mode("mosaic")
        monkeypatch.setenv(registry.ENV_VAR, "bogus")
        with pytest.raises(ValueError):
            registry.resolve_mode(None)

    def test_coerce_mode_mapping(self):
        assert registry.coerce_mode(True, None) == "interpret"
        assert registry.coerce_mode(False, None) == "compiled"
        assert registry.coerce_mode(True, "xla") == "xla"     # mode wins
        assert registry.coerce_mode(None, None) is None

    def test_legacy_interpret_loses_to_process_pin(self, fake_op,
                                                   monkeypatch):
        """The legacy interpret bool is a backend-derived DEFAULT, so
        the CI-matrix env pin overrides it; explicit mode= still wins."""
        name, _ = fake_op
        monkeypatch.setenv(registry.ENV_VAR, "xla")
        assert registry.legacy_mode(name, True, None) == "xla"
        assert registry.legacy_mode(name, True, "interpret") == "interpret"
        monkeypatch.delenv(registry.ENV_VAR)
        assert registry.legacy_mode(name, True, None) == "interpret"
        assert registry.legacy_mode(name, None, None) is None


def test_all_call_sites_registered():
    """The tentpole contract: every Pallas call-site op is in the
    registry with an XLA reference to test against."""
    expected = {"cic_splat", "cic_gather", "knn_dist_tiles", "tsne_step",
                "segment_reduce"}
    assert expected <= set(registry.list_ops())
    for op in expected:
        assert "xla" in registry.modes_of(op), op
        assert "compiled" in registry.modes_of(op), op


# ------------------------------------------------- per-op mode equivalence
# non-divisible sizes exercise the padding tails of every wrapper
@pytest.mark.parametrize("n", [37, 1000])
@pytest.mark.parametrize("mode", RUNNABLE)
def test_cic_modes_equivalent(n, mode):
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    g = 16
    pts = jax.random.uniform(k1, (n, 2), jnp.float32, 0.0, g - 1.001)
    i0 = jnp.floor(pts).astype(jnp.int32)
    f = pts - jnp.floor(pts)
    vals = jax.random.normal(k2, (n, 3), jnp.float32)
    fields = jax.random.normal(k3, (3, g, g), jnp.float32)
    ref_s = ops.cic_splat(i0, f, vals, g, mode="xla")
    ref_g = ops.cic_gather(fields, i0, f, mode="xla")
    got_s = ops.cic_splat(i0, f, vals, g, mode=mode)
    got_g = ops.cic_gather(fields, i0, f, mode=mode)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(ref_s),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_g), np.asarray(ref_g),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,block", [
    (100, 32),     # padding tail
    (50, 128),     # block >= n: one oversized tile covers everything
])
@pytest.mark.parametrize("mode", RUNNABLE)
def test_tsne_step_modes_equivalent(n, block, mode):
    k1, k2 = jax.random.split(jax.random.key(1))
    x = jax.random.normal(k1, (n, 4), jnp.float32)
    y = jax.random.normal(k2, (n, 2), jnp.float32)
    beta = jnp.ones((n,), jnp.float32)
    zp = jnp.full((n,), float(n), jnp.float32)
    ref_f, ref_kl = ops.tsne_step_fused(x, y, beta, zp, block=block,
                                        mode="xla", return_kl=True)
    got_f, got_kl = ops.tsne_step_fused(x, y, beta, zp, block=block,
                                        mode=mode, return_kl=True)
    np.testing.assert_allclose(np.asarray(got_f), np.asarray(ref_f),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(got_kl), float(ref_kl),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mode", RUNNABLE)
def test_knn_dist_tiles_modes_equivalent(mode):
    k1, k2, k3 = jax.random.split(jax.random.key(2), 3)
    t, b, d = 3, 16, 4
    qx = jax.random.normal(k1, (t, b, d), jnp.float32)
    qid = jnp.arange(t * b, dtype=jnp.int32).reshape(t, b)
    cx = jax.random.normal(k2, (t, 3 * b, d), jnp.float32)
    cid = jax.random.randint(k3, (t, 3 * b), -1, t * b, dtype=jnp.int32)
    ref = knn_tile.distance_tiles(qx, qid, cx, cid, mode="xla")
    got = knn_tile.distance_tiles(qx, qid, cx, cid, mode=mode)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("rows,fan,rpb", [
    (33, 5, 8),     # non-divisible row-block tail
    (4, 7, 128),    # rows_per_block >= n: single oversized block
    (1, 64, 8),     # everything in one row
])
@pytest.mark.parametrize("mode", RUNNABLE)
def test_segment_reduce_modes_equivalent(rows, fan, rpb, mode):
    rng = np.random.default_rng(3)
    # ragged bounds: random fan-out around `fan`, including empty rows
    sizes = rng.integers(0, 2 * fan + 1, size=rows)
    bounds = jnp.asarray(np.concatenate([[0], np.cumsum(sizes)]),
                         jnp.int32)
    e = int(bounds[-1])
    vals = jnp.asarray(rng.normal(size=(e, 2)).astype(np.float32))
    ref = coo.segment_reduce(vals, bounds, mode="xla")
    if mode == "xla":
        got = coo.segment_reduce(vals, bounds, mode="xla")
    else:
        impl = registry.get("segment_reduce", mode)
        got = impl.fn(vals, bounds, rows_per_block=rpb, edge_chunk=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# -------------------------------------------- segment reduce: bit-for-bit
@pytest.mark.parametrize("rows,fan", [(1, 16), (16, 0), (64, 3), (33, 9)])
def test_segment_reduce_bitwise_on_exact_payloads(rows, fan):
    """With integer-valued fp32 payloads (< 2^24) every addition is
    exact, so the fused kernel and the cumsum-difference path must agree
    BIT FOR BIT on every shape — empty rows, single row, ragged tails."""
    rng = np.random.default_rng(4)
    sizes = rng.integers(0, 2 * fan + 1, size=rows) if fan else \
        np.zeros(rows, np.int64)   # fan=0: all rows empty
    bounds = jnp.asarray(np.concatenate([[0], np.cumsum(sizes)]),
                         jnp.int32)
    e = int(bounds[-1])
    vals = jnp.asarray(
        rng.integers(-1000, 1000, size=(e, 2)).astype(np.float32))
    ref = coo.segment_reduce(vals, bounds)          # cumsum path
    got = segred.segment_reduce_pallas(vals, bounds, rows_per_block=8,
                                       edge_chunk=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_segment_reduce_1d_payload_and_empty():
    vals = jnp.asarray([1.0, 2.0, 3.0, 4.0], jnp.float32)
    bounds = jnp.asarray([0, 2, 2, 4], jnp.int32)
    got = segred.segment_reduce_pallas(vals, bounds, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), [3.0, 0.0, 7.0])
    empty = segred.segment_reduce_pallas(
        jnp.zeros((0,), jnp.float32), jnp.zeros((1,), jnp.int32),
        interpret=True)
    assert empty.shape == (0,)


# ------------------------------------------------------- dtype promotion
@pytest.mark.parametrize("dtype", [jnp.float16, jnp.bfloat16])
def test_segment_reduce_kernel_accumulates_fp32(dtype):
    """fp16/bf16 payloads accumulate in fp32 inside the kernel: a row of
    [256, 1, 1, ..., 1] sums to 256+k exactly in fp32, while native
    low-precision accumulation would round every +1 away."""
    k = 8
    vals = jnp.asarray([256.0] + [1.0] * k, jnp.float32).astype(dtype)
    bounds = jnp.asarray([0, k + 1], jnp.int32)
    out = segred.segment_reduce_pallas(vals, bounds, interpret=True)
    assert out.dtype == dtype
    assert float(out[0].astype(jnp.float32)) == 256.0 + k


@pytest.mark.parametrize("dtype", [jnp.float16, jnp.bfloat16])
def test_tsne_step_promotes_to_fp32(dtype):
    n = 40
    k1, k2 = jax.random.split(jax.random.key(5))
    x = jax.random.normal(k1, (n, 4), jnp.float32)
    y = jax.random.normal(k2, (n, 2), jnp.float32)
    beta = jnp.ones((n,), jnp.float32)
    zp = jnp.full((n,), float(n), jnp.float32)
    ref = ops.tsne_step_fused(x, y, beta, zp, mode="interpret")
    got = ops.tsne_step_fused(x.astype(dtype), y.astype(dtype), beta, zp,
                              mode="interpret")
    assert got.dtype == jnp.float32          # fp32 accumulation out
    # low-precision INPUT costs precision, fp32 ACCUMULATION caps it
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=0.15, atol=0.05)


@pytest.mark.parametrize("dtype", [jnp.float16, jnp.bfloat16])
def test_cic_splat_promotes_to_fp32(dtype):
    n, g = 100, 8
    k1, k2 = jax.random.split(jax.random.key(6))
    pts = jax.random.uniform(k1, (n, 2), jnp.float32, 0.0, g - 1.001)
    i0 = jnp.floor(pts).astype(jnp.int32)
    f = pts - jnp.floor(pts)
    vals = jax.random.normal(k2, (n, 2), jnp.float32)
    ref = ops.cic_splat(i0, f, vals, g, mode="interpret")
    got = ops.cic_splat(i0, f.astype(dtype), vals, g, mode="interpret")
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=0.02, atol=0.02)


# ------------------------------------------------------------- jaxpr pins
def _count_primitive(jaxpr, name):
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            n += 1
        for p in eqn.params.values():
            vals = p if isinstance(p, (list, tuple)) else [p]
            for v in vals:
                if hasattr(v, "jaxpr"):
                    n += _count_primitive(v.jaxpr, name)
                elif hasattr(v, "eqns"):
                    n += _count_primitive(v, name)
    return n


def _pallas_calls(fn, *args):
    return _count_primitive(jax.make_jaxpr(fn)(*args).jaxpr, "pallas_call")


def test_xla_mode_traces_contain_zero_pallas_calls():
    """Forcing kernel_mode="xla" must produce pure-XLA programs — the CI
    matrix leg depends on it actually avoiding the Pallas machinery."""
    n, g = 64, 8
    k1, k2, k3 = jax.random.split(jax.random.key(7), 3)
    pts = jax.random.uniform(k1, (n, 2), jnp.float32, 0.0, g - 1.001)
    i0 = jnp.floor(pts).astype(jnp.int32)
    f = pts - jnp.floor(pts)
    vals = jax.random.normal(k2, (n, 2), jnp.float32)
    fields = jax.random.normal(k3, (2, g, g), jnp.float32)
    y = jax.random.normal(k2, (n, 2), jnp.float32)
    ones = jnp.ones((n,), jnp.float32)
    qx = jax.random.normal(k1, (2, 8, 4), jnp.float32)
    qid = jnp.arange(16, dtype=jnp.int32).reshape(2, 8)
    cx = jax.random.normal(k2, (2, 24, 4), jnp.float32)
    cid = jnp.arange(48, dtype=jnp.int32).reshape(2, 24) % 16
    sv = jax.random.normal(k3, (40, 2), jnp.float32)
    sb = jnp.asarray([0, 10, 25, 40], jnp.int32)

    cases = {
        "cic_splat": lambda: ops.cic_splat(i0, f, vals, g, mode="xla"),
        "cic_gather": lambda: ops.cic_gather(fields, i0, f, mode="xla"),
        "tsne_step": lambda: ops.tsne_step_fused(pts, y, ones,
                                                 ones * n, mode="xla"),
        "knn_dist_tiles": lambda: knn_tile.distance_tiles(
            qx, qid, cx, cid, mode="xla"),
        "segment_reduce": lambda: coo.segment_reduce(sv, sb, mode="xla"),
    }
    for op, fn in cases.items():
        assert _pallas_calls(fn) == 0, \
            f"{op}: mode='xla' trace still contains pallas_call"
    # sanity: the pin would catch a regression — interpret DOES trace one
    assert _pallas_calls(
        lambda: ops.cic_splat(i0, f, vals, g, mode="interpret")) >= 1


# ------------------------------------------------------- config plumbing
def test_sns_config_validates_kernel_mode():
    with pytest.raises(ValueError, match="kernel_mode"):
        pipeline.SnsConfig(kernel_mode="mosaic")


def test_resolve_embed_cfg_threads_kernel_mode():
    cfg = pipeline.SnsConfig(embedder="tsne", embed_backend="sparse",
                             kernel_mode="xla")
    ecfg = pipeline.resolve_embed_cfg(cfg)
    assert ecfg.kernel_mode == "xla"
    assert ecfg.ann is not None and ecfg.ann.kernel_mode == "xla"
    ucfg = pipeline.resolve_embed_cfg(
        dataclasses.replace(cfg, embedder="umap"))
    assert ucfg.kernel_mode == "xla"
    # auto leaves the ANN config alone (None = defer to tile/interpret)
    auto = pipeline.resolve_embed_cfg(
        dataclasses.replace(cfg, kernel_mode="auto"))
    assert auto.kernel_mode == "auto" and auto.ann is None


def test_run_tsne_rejects_bad_kernel_mode():
    cfg = tsne_mod.TsneConfig(n_iter=1, kernel_mode="bogus")
    x = jnp.zeros((8, 3), jnp.float32)
    with pytest.raises(ValueError, match="kernel_mode"):
        tsne_mod.run_tsne(jax.random.key(0), x, cfg)


@pytest.mark.parametrize("mode", ["interpret", "xla"])
def test_sparse_tsne_runs_under_forced_mode(mode):
    """End-to-end: the sparse tSNE loop (cic + tsne kernels + segment
    reduce) runs under each CPU-runnable forced tier and produces the
    same embedding as auto (which resolves to one of these)."""
    x = jnp.asarray(np.random.default_rng(8).normal(
        size=(64, 4)).astype(np.float32))
    base = tsne_mod.TsneConfig(backend="sparse", n_iter=3, knn=4,
                               grid_size=16, perplexity=4.0)
    cfg = dataclasses.replace(base, kernel_mode=mode)
    emb, _ = tsne_mod.run_tsne(jax.random.key(0), x, cfg)
    ref, _ = tsne_mod.run_tsne(jax.random.key(0), x, base)
    assert emb.shape == ref.shape
    np.testing.assert_allclose(np.asarray(emb), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("mode", ["interpret", "xla"])
def test_umap_runs_under_forced_mode(mode):
    x = jnp.asarray(np.random.default_rng(9).normal(
        size=(48, 4)).astype(np.float32))
    base = umap_mod.UmapConfig(n_epochs=2, n_neighbors=4)
    cfg = dataclasses.replace(base, kernel_mode=mode)
    emb = umap_mod.run_umap(jax.random.key(0), x, cfg)
    ref = umap_mod.run_umap(jax.random.key(0), x, base)
    np.testing.assert_allclose(np.asarray(emb), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_ann_kernel_mode_forces_distance_tier():
    x = jnp.asarray(np.random.default_rng(10).normal(
        size=(128, 4)).astype(np.float32))
    base = ann_mod.AnnConfig(tile="xla")
    ref_i, _ = ann_mod.ann_knn_graph(x, 4, dataclasses.replace(
        base, kernel_mode="interpret"))
    ref_x, _ = ann_mod.ann_knn_graph(x, 4, dataclasses.replace(
        base, kernel_mode="xla"))
    np.testing.assert_array_equal(np.asarray(ref_i), np.asarray(ref_x))


def test_ann_pallas_tile_follows_the_registry():
    """AnnConfig(tile="pallas") with no interpret flag lets the registry
    pick the kernel tier (the interpreter on CPU) and matches XLA."""
    x = jnp.asarray(np.random.default_rng(11).normal(
        size=(128, 4)).astype(np.float32))
    assert ann_mod.AnnConfig().interpret is None
    got, _ = ann_mod.ann_knn_graph(x, 4, ann_mod.AnnConfig(tile="pallas"))
    ref, _ = ann_mod.ann_knn_graph(x, 4, ann_mod.AnnConfig(tile="xla"))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# --------------------------------------------------------- tile params
def test_tile_params_table_and_cache(tmp_path):
    p = registry.tile_params("cic_splat", backend="cpu")
    assert p["block_items"] == 1024
    assert registry.tile_params("tsne_step", backend="tpu")["block"] == 128
    cache = tmp_path / "tune.json"
    registry.record_autotune("cic_splat", {"block_items": 2048},
                             backend="cpu", bucket="65536x2",
                             path=str(cache))
    got = registry.tile_params("cic_splat", backend="cpu",
                               shape=(60000, 2), cache_path=str(cache))
    assert got["block_items"] == 2048          # exact-bucket hit
    other = registry.tile_params("cic_splat", backend="cpu",
                                 shape=(100, 2), cache_path=str(cache))
    assert other["block_items"] == 1024        # different bucket -> table


def test_tile_params_read_only_the_committed_table(tmp_path, monkeypatch):
    """No environment variable or file under HOME reaches the program's
    tile sizes: only an explicit cache path does."""
    stray = tmp_path / "tune.json"
    registry.record_autotune("cic_splat", {"block_items": 4096},
                             path=str(stray), backend="cpu")
    home_cache = tmp_path / ".cache" / "sns_kernel_autotune.json"
    home_cache.parent.mkdir()
    home_cache.write_text(stray.read_text())
    monkeypatch.setenv("HOME", str(tmp_path))
    monkeypatch.setenv("SNS_KERNEL_CACHE", str(stray))
    assert registry.tile_params("cic_splat", backend="cpu",
                                shape=(1000, 3))["block_items"] == 1024
    assert registry.tile_params("cic_splat", backend="cpu",
                                cache_path=str(stray))["block_items"] == 4096


def test_shape_bucket():
    assert registry.shape_bucket((1000, 2)) == "1024x2"
    assert registry.shape_bucket(()) == "scalar"
    assert registry.shape_bucket((1,)) == "1"


def test_autotune_op_skips_raising_candidates(tmp_path):
    cache = tmp_path / "tune.json"

    def measure(params):
        if params["k"] == 1:
            raise RuntimeError("VMEM")
        return params["k"] * 0.5

    best = registry.autotune_op("cic_splat", [{"k": 1}, {"k": 2}, {"k": 4}],
                                measure, backend="cpu",
                                cache_path=str(cache))
    assert best == {"k": 2}
    with pytest.raises(registry.KernelUnavailableError):
        registry.autotune_op(
            "cic_splat", [{"k": 1}],
            lambda p: (_ for _ in ()).throw(RuntimeError("x")),
            backend="cpu", cache_path=str(cache))
