"""The benchmark's own point generator: one mixture per stream.

A configuration's ``data`` block describes the survey stand-in: a
Gaussian mixture of ``n_clusters`` clusters (standard deviation
``cluster_std``) over a uniform background that takes
``background_frac`` of the points, all clipped to [0, 1]^dims.  The
cluster centres are drawn once per stream from its seed, and every
chunk of the stream draws its points from that one mixture with a
generator of its own (``SeedSequence([seed, draw, 1 + chunk])``), so chunks
are disjoint draws of the same distribution.  A second sequence of
draws from the same mixture (``draw``, such as the queries of a served
map) takes its own ``draw`` number.

Every chunk has the same composition: ``int(n * background_frac)``
background points and the rest split as evenly as the clusters allow,
in an order shuffled by the chunk's generator.  So two seeds give the
same amount of background and cluster mass, in other places.
"""
from __future__ import annotations

import dataclasses

import numpy as np

BOX_MARGIN = 0.1     # centres lie in [0.1, 0.9]^dims


@dataclasses.dataclass(frozen=True)
class Mixture:
    dims: int
    n_clusters: int
    cluster_std: float
    background_frac: float

    @classmethod
    def from_config(cls, data: dict) -> "Mixture":
        return cls(dims=int(data["dims"]), n_clusters=int(data["n_clusters"]),
                   cluster_std=float(data["cluster_std"]),
                   background_frac=float(data["background_frac"]))


def _rng(*words: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(w) for w in words]))


class Stream:
    """Chunks of one mixture: ``chunk(i, n)`` is the i-th disjoint draw."""

    def __init__(self, mix: Mixture, seed: int):
        self.mix = mix
        self.seed = int(seed)
        self.centers = _rng(self.seed).uniform(
            BOX_MARGIN, 1.0 - BOX_MARGIN,
            size=(mix.n_clusters, mix.dims)).astype(np.float32)

    def chunk(self, i: int, n: int, draw: int = 0) -> np.ndarray:
        """(n, dims) float32 points of chunk ``i`` of sequence ``draw``."""
        mix = self.mix
        rng = _rng(self.seed, int(draw), 1 + int(i))
        n_bg = int(n * mix.background_frac)
        n_cl = n - n_bg
        comp = np.arange(n_cl) % mix.n_clusters
        pts = np.empty((n, mix.dims), np.float32)
        pts[:n_bg] = rng.random((n_bg, mix.dims), dtype=np.float32)
        noise = rng.standard_normal((n_cl, mix.dims), dtype=np.float32)
        pts[n_bg:] = self.centers[comp] + np.float32(mix.cluster_std) * noise
        np.clip(pts, 0.0, 1.0, out=pts)
        return pts[rng.permutation(n)]

    def pool(self, n_chunks: int, n: int, draw: int = 0) -> list:
        return [self.chunk(i, n, draw) for i in range(n_chunks)]
