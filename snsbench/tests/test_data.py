"""The benchmark's generator: one mixture per stream, disjoint chunks."""
import numpy as np

from snsbench import data

MIX = data.Mixture(dims=8, n_clusters=10, cluster_std=0.02,
                   background_frac=0.3)


def test_same_seed_same_points():
    a = data.Stream(MIX, 2**31 + 77).chunk(3, 5000)
    b = data.Stream(MIX, 2**31 + 77).chunk(3, 5000)
    assert a.dtype == np.float32 and a.shape == (5000, 8)
    np.testing.assert_array_equal(a, b)


def test_other_seed_other_mixture():
    a, b = data.Stream(MIX, 1), data.Stream(MIX, 2)
    assert not np.allclose(a.centers, b.centers)


def _near_center(points, centers, tol=0.1):
    d = np.linalg.norm(points[:, None, :] - centers[None], axis=2)
    return d.min(axis=1) < tol


def test_one_mixture_across_chunks():
    s = data.Stream(MIX, 5)
    for i in (0, 7, 100):
        c = s.chunk(i, 20000)
        # every chunk puts its cluster mass on the stream's centres
        near = _near_center(c, s.centers)
        assert 0.68 < near.mean() < 0.75
        assert np.all((c >= 0) & (c <= 1))


def test_chunks_are_disjoint_draws():
    s = data.Stream(MIX, 5)
    a, b = s.chunk(0, 20000), s.chunk(1, 20000)
    rows = {r.tobytes() for r in a}
    assert not any(r.tobytes() in rows for r in b)
    q = s.chunk(0, 20000, draw=9)
    assert not np.array_equal(q, a)


def test_fixed_composition():
    s = data.Stream(MIX, 5)
    for i in range(3):
        c = s.chunk(i, 10000)
        assert _near_center(c, s.centers, tol=0.15).sum() >= 7000
