"""The references against brute force and against the program's own
definitions at small sizes."""
import collections
import math

import numpy as np
import pytest

from snsbench import data, reference

MIX = data.Mixture(dims=8, n_clusters=10, cluster_std=0.02,
                   background_frac=0.3)


def _brute_key(p, bins):
    bits = max(1, math.ceil(math.log2(bins)))
    key = 0
    for v in p:
        q = min(max(int(np.floor(np.float32(v) * np.float32(bins))), 0),
                bins - 1)
        key = (key << bits) | q
    return key


def test_exact_counts_match_a_brute_count():
    s = data.Stream(MIX, 11)
    chunks = [s.chunk(i, 3000) for i in range(3)]
    times = [2, 0, 1]
    uniq, counts = reference.exact_counts(chunks, times, 25)
    brute = collections.Counter()
    for c, t in zip(chunks, times):
        for p in c[:t and None]:
            brute[_brute_key(p, 25)] += t
    assert dict(zip(uniq.tolist(), counts.tolist())) == dict(brute)


def test_cell_keys_match_the_program_quantizer():
    from repro.core import quantize
    pts = data.Stream(MIX, 3).chunk(0, 4000)
    pts[:5] = [[1.0] * 8, [0.0] * 8, [0.04] * 8, [0.9999999] * 8,
               [0.5] * 8]
    grid = quantize.GridSpec(dims=8, bins=25, lo=(0.0,) * 8, hi=(1.0,) * 8)
    hi, lo = quantize.points_to_keys(grid, pts)
    prog = (np.asarray(hi, np.uint64) << np.uint64(32)) | \
        np.asarray(lo, np.uint64)
    np.testing.assert_array_equal(reference.cell_keys(pts, 25), prog)


def test_heavy_hitter_numbers_on_an_exact_answer():
    uniq = np.arange(10, dtype=np.uint64)
    exact = np.array([50, 40, 30, 20, 10, 5, 4, 3, 2, 1])
    n = reference.heavy_hitter_numbers(uniq[:4], exact[:4].astype(float),
                                       0.0, uniq, exact, 4, 4)
    assert n["served_gap"] == 0 and n["foreign_cells"] == 0
    assert n["sketch_err_sigma"] == 0 and n["missed_over_allowed"] == 0
    # leaving out the heaviest cell and serving a light one is caught
    bad = reference.heavy_hitter_numbers(
        np.array([1, 2, 3, 9], np.uint64), np.array([40., 30, 20, 1]),
        0.0, uniq, exact, 4, 4)
    assert bad["missed_over_allowed"] == pytest.approx(50.0)
    assert bad["top_half_missed_share"] == 0.5 and bad["first_missed_rank"] == 0.25
    assert n["top_half_missed_share"] == 0 and n["first_missed_rank"] == 1.25


def test_a_served_set_short_of_the_top_half_is_caught():
    """Real cells with exact counts, but the light ones: only
    ``top_half_missed_share`` reads it."""
    uniq = np.arange(8, dtype=np.uint64)
    exact = np.array([80, 70, 60, 50, 4, 3, 2, 1])
    n = reference.heavy_hitter_numbers(uniq[4:], exact[4:].astype(float),
                                       0.0, uniq, exact, 4, 4)
    assert n["served_gap"] == 0 and n["foreign_cells"] == 0
    assert n["sketch_err_sigma"] == 0
    assert n["top_half_missed_share"] == 1 and n["first_missed_rank"] == 0.25
