"""Hypercube quantizer: points -> grid cells -> packed 64-bit keys.

The paper (§III-1) encloses the data in a D-dimensional hypercube with M
linear bins per axis and concatenates the quantized coordinates into a
feature vector fed to Count Sketch.  We pack with *bit fields* rather than
base-M positional encoding so that unpacking is shift/mask (no 64-bit
division, which TPUs lack): each coordinate gets ceil(log2(M)) bits.

Constraint: D * ceil(log2(M)) <= 64.  The paper's regime (D < 20, M ~ 8-32)
always satisfies this; config validation enforces it.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import u64


@dataclasses.dataclass(frozen=True)
class GridSpec:
    """A fitted quantization grid.  Hashable (corner coords stored as
    tuples) so it can ride as a static jit argument into Pallas wrappers."""
    dims: int
    bins: int                      # M, linear bins per axis
    lo: Tuple[float, ...]          # (D,) lower corner
    hi: Tuple[float, ...]          # (D,) upper corner
    bits_per_dim: int = 0

    def __post_init__(self):
        object.__setattr__(self, "lo",
                           tuple(float(v) for v in np.asarray(self.lo).ravel()))
        object.__setattr__(self, "hi",
                           tuple(float(v) for v in np.asarray(self.hi).ravel()))
        bits = max(1, math.ceil(math.log2(self.bins)))
        object.__setattr__(self, "bits_per_dim", bits)
        if self.dims * bits > 64:
            raise ValueError(
                f"cannot pack D={self.dims} dims x {bits} bits into 64-bit keys; "
                f"reduce bins (M={self.bins}) or dims (paper regime is D<20)")

    @property
    def lo_arr(self) -> np.ndarray:
        return np.asarray(self.lo, np.float32)

    @property
    def hi_arr(self) -> np.ndarray:
        return np.asarray(self.hi, np.float32)

    @property
    def cell_size(self) -> np.ndarray:
        return (self.hi_arr - self.lo_arr) / self.bins

    @property
    def volume(self) -> float:
        """Total number of cells V = M^D (paper §III-2)."""
        return float(self.bins) ** self.dims


def fit_grid(points: jnp.ndarray, bins: int,
             lo: Optional[np.ndarray] = None,
             hi: Optional[np.ndarray] = None,
             pad: float = 1e-3) -> GridSpec:
    """Fit the enclosing hypercube.  `lo`/`hi` may be supplied (geo-distributed
    sites must agree on the grid; see core/geo.py) — then no data pass is made."""
    d = int(points.shape[-1])
    if lo is None:
        lo = np.asarray(jnp.min(points.reshape(-1, d), axis=0), np.float32)
    if hi is None:
        hi = np.asarray(jnp.max(points.reshape(-1, d), axis=0), np.float32)
    lo = np.asarray(lo, np.float32)
    hi = np.asarray(hi, np.float32)
    span = np.maximum(hi - lo, 1e-12)
    return GridSpec(dims=d, bins=int(bins), lo=lo - pad * span, hi=hi + pad * span)


def fit_grid_streaming(chunks, bins: int, pad: float = 1e-3) -> GridSpec:
    """Fit the enclosing hypercube from a chunk stream — the first pass of
    the two-pass streaming pipeline.  Chunked running min/max, so no stage
    ever holds the full array; min/max are associative, which makes the
    result bit-identical to :func:`fit_grid` on the concatenated points.

    ``chunks``: an iterable of (n_i, D) arrays, or a callable returning one
    (the re-iterable form used by ``pipeline.run_streaming``).
    """
    if callable(chunks):
        chunks = chunks()
    lo = hi = None
    d = None
    for c in chunks:
        c = np.asarray(c, np.float32)
        if c.ndim != 2:
            c = c.reshape(-1, c.shape[-1])
        if d is None:
            d = c.shape[1]
        if c.shape[0] == 0:        # empty shard batch — min has no identity
            continue
        clo, chi = c.min(axis=0), c.max(axis=0)
        lo = clo if lo is None else np.minimum(lo, clo)
        hi = chi if hi is None else np.maximum(hi, chi)
    if lo is None:
        raise ValueError("fit_grid_streaming: empty chunk stream")
    span = np.maximum(hi - lo, 1e-12)
    return GridSpec(dims=d, bins=int(bins), lo=lo - pad * span,
                    hi=hi + pad * span)


def quantize(grid: GridSpec, points: jnp.ndarray) -> jnp.ndarray:
    """(..., D) float points -> (..., D) uint32 bin coordinates in [0, M)."""
    lo = jnp.asarray(grid.lo_arr)
    inv = jnp.asarray(grid.bins / (grid.hi_arr - grid.lo_arr), jnp.float32)
    idx = jnp.floor((points - lo) * inv)
    idx = jnp.clip(idx, 0, grid.bins - 1)
    return idx.astype(jnp.uint32)


def pack(grid: GridSpec, coords: jnp.ndarray) -> u64.U64:
    """(..., D) uint32 coords -> packed 64-bit keys (hi, lo) of shape (...)."""
    bits = grid.bits_per_dim
    hi = jnp.zeros(coords.shape[:-1], jnp.uint32)
    lo = jnp.zeros(coords.shape[:-1], jnp.uint32)
    key = (hi, lo)
    for i in range(grid.dims):
        key = u64.shl(key, bits)
        key = u64.add_u32(key, coords[..., i])
    return key


def unpack(grid: GridSpec, key: u64.U64) -> jnp.ndarray:
    """Packed keys (...) -> (..., D) uint32 coords (inverse of `pack`)."""
    bits = grid.bits_per_dim
    mask = np.uint32((1 << bits) - 1)
    outs = []
    k = key
    for _ in range(grid.dims):
        outs.append(u64.bitand_u32(k, mask))
        k = u64.shr(k, bits)
    return jnp.stack(outs[::-1], axis=-1)


def cell_center(grid: GridSpec, coords: jnp.ndarray) -> jnp.ndarray:
    """(..., D) uint32 coords -> float32 cell centers in data space."""
    cs = jnp.asarray(grid.cell_size)
    return jnp.asarray(grid.lo_arr) + (coords.astype(jnp.float32) + 0.5) * cs


@jax.named_scope("sns.fold.keys")
def points_to_keys(grid: GridSpec, points: jnp.ndarray) -> u64.U64:
    return pack(grid, quantize(grid, points))


def collision_rate(volume: float, num_hh: int, dims: int) -> Tuple[float, float]:
    """Paper §III-2 Poisson contact-neighbourhood collision model.

    K heavy hitters on a grid of V cells; each cell's contact neighbourhood
    is the 3^D hypercube around it, so the HH density per neighbourhood is
    rho = K * 3^D / V.  A *random collision* is a neighbourhood containing
    two or more HHs:  P(coll) = P(N>=2) = 1 - e^-rho - rho*e^-rho, and the
    expected number of collided HHs is C = K * P(coll).  This reproduces the
    paper's numbers: K=1e4, D=10, M=8 -> C~1057; M=16 -> C~0.00144.
    """
    lam = num_hh / volume
    w = 3.0 ** dims
    rho = w * lam
    p_ge2 = 1.0 - math.exp(-rho) - rho * math.exp(-rho)
    return rho, num_hh * p_ge2


def collision_rate_text(volume: float, num_hh: int, dims: int
                        ) -> Tuple[float, float]:
    """The formula as WRITTEN in the paper's text: C = K·P(>0) with
    P(>0) = 1 - e^-rho.  Note: the paper's published numbers (1057,
    0.00144) do NOT follow this formula — they follow :func:`collision_rate`
    (P(N>=2)).  Monte-Carlo placement (benchmarks/bench_collision_model)
    supports the *text* formula for per-HH collision counting; we keep
    both and document the discrepancy in EXPERIMENTS.md.
    """
    lam = num_hh / volume
    w = 3.0 ** dims
    rho = w * lam
    return rho, num_hh * (1.0 - math.exp(-rho))
