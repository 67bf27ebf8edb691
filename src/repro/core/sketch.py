"""Count Sketch (Charikar-Chen-Farach-Colton) as a JAX pytree.

The paper's four operations (§III-1): init / update / estimate / merge.
The sketch is a *linear operator* over the frequency vector — merging two
sketches built with the same hashes is element-wise addition of the tables.
That linearity is the entire geo-distributed story of the paper, and here
it is also what makes the TPU story work: ``merge == jax.lax.psum``.

Three update paths are provided:

* :func:`update` — XLA ``scatter-add`` per row (flattened to one scatter).
  Simple, always correct, and the gradient-compression path.
* :func:`update_runs` — THE bulk/streaming path: scatter of pre-deduped
  sorted key runs (``candidates.KeyRuns``).  The streaming ingest engine
  (``core.stream.ingest_step``) sorts + run-length-encodes each chunk
  exactly once via ``candidates.sorted_runs`` and feeds the same runs to
  this scatter AND to the reservoir merge — one sort per chunk total.
* :func:`update_sorted` — convenience wrapper: ``sorted_runs`` +
  ``update_runs`` for callers holding raw keys.  On TPU, ``sort`` is a
  native bitonic network and turns the random-access scatter into
  sequential memory traffic; preferred over :func:`update` when the number
  of items per call is ≫ the number of distinct cells (the paper's
  regime: 10⁸ points → 10⁵ cells) — but if a top-k/reservoir stage also
  needs the keys, build the runs once and use :func:`update_runs`.

All are exactly equivalent (tested).  The Pallas kernel in
``repro.kernels.sketch_update`` is a fused low-latency small-batch path.

Table dtype: float32 by default (exact integer counting up to 2²⁴ per
bucket per shard; shards hold ≪ 2²⁴ items per bucket in practice, and the
gradient-compression use-case needs floats).  Use int32 for exact counting
of huge single-shard streams.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing, u64
from repro.core.candidates import KeyRuns, sorted_runs


class CountSketch(NamedTuple):
    """Sketch state.  A pytree: ``table`` + hash params; static geometry
    travels in the aux fields (python ints, hashable)."""
    table: jnp.ndarray                 # (R, C) float32/int32
    params: hashing.MulShiftParams     # R independent hash fns

    @property
    def rows(self) -> int:
        return self.table.shape[0]

    @property
    def cols(self) -> int:
        return self.table.shape[1]

    @property
    def log2_cols(self) -> int:
        return int(self.table.shape[1]).bit_length() - 1


def init(key: jax.Array, rows: int, log2_cols: int,
         dtype=jnp.float32) -> CountSketch:
    """``init(R, C)`` — zero table, R fresh hash functions, C = 2**log2_cols.

    Power-of-two columns so the bucket hash is a shift (no 64-bit modulo,
    which TPUs lack).  The paper's 2·10⁵ columns becomes 2¹⁸ = 262144.
    """
    if not (1 <= log2_cols <= 31):
        raise ValueError(f"log2_cols must be in [1, 31], got {log2_cols}")
    params = hashing.make_params(key, rows)
    table = jnp.zeros((rows, 1 << log2_cols), dtype)
    return CountSketch(table=table, params=params)


def _hashes(sk: CountSketch, key_hi: jnp.ndarray, key_lo: jnp.ndarray
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(items,) keys -> bucket ids (R, items) uint32 and signs (R, items) int32."""
    buckets = hashing.bucket_hash(sk.params, key_hi, key_lo, sk.log2_cols)
    signs = hashing.sign_hash(sk.params, key_hi, key_lo)
    return buckets, signs


def update(sk: CountSketch, key_hi: jnp.ndarray, key_lo: jnp.ndarray,
           values: Optional[jnp.ndarray] = None,
           mask: Optional[jnp.ndarray] = None) -> CountSketch:
    """``update(s_i)`` for a batch of items: S[r, h1_r(i)] += h2_r(i)·v_i.

    ``values`` defaults to 1 (pure counting).  ``mask`` zeroes out padding
    items (static-shape streaming needs ragged tails).
    """
    items = key_hi.shape[0]
    buckets, signs = _hashes(sk, key_hi, key_lo)
    v = jnp.ones((items,), sk.table.dtype) if values is None \
        else values.astype(sk.table.dtype)
    if mask is not None:
        v = v * mask.astype(sk.table.dtype)
    upd = signs.astype(sk.table.dtype) * v[None, :]          # (R, items)
    # one scatter over the flattened (R*C) table
    flat_idx = (jnp.arange(sk.rows, dtype=jnp.uint32)[:, None]
                << np.uint32(sk.log2_cols)) | buckets
    flat = sk.table.reshape(-1).at[flat_idx.reshape(-1)].add(
        upd.reshape(-1), mode="drop")
    return sk._replace(table=flat.reshape(sk.table.shape))


@jax.named_scope("sns.fold.scatter")
def update_runs(sk: CountSketch, runs: KeyRuns) -> CountSketch:
    """Scatter pre-deduped sorted key runs into the table — the bulk path.

    ``runs`` comes from ``candidates.sorted_runs``; the caller pays that one
    sort and reuses the runs for the reservoir merge too (the fused ingest
    step).  Dead slots carry count 0, so they scatter nothing."""
    return update(sk, runs.key_hi, runs.key_lo, values=runs.count,
                  mask=runs.live)


def update_sorted(sk: CountSketch, key_hi: jnp.ndarray, key_lo: jnp.ndarray,
                  values: Optional[jnp.ndarray] = None,
                  mask: Optional[jnp.ndarray] = None) -> CountSketch:
    """Sort-based update from raw keys: aggregate duplicates, scatter once.

    ``sorted_runs`` (sort → segment boundaries → per-run summed value)
    + :func:`update_runs` (scatter of ``num_runs ≤ items`` deduped
    updates).  Equivalent to :func:`update`.
    """
    runs = sorted_runs(key_hi, key_lo, values=values, mask=mask,
                       dtype=sk.table.dtype)
    return update_runs(sk, runs)


def estimate(sk: CountSketch, key_hi: jnp.ndarray, key_lo: jnp.ndarray
             ) -> jnp.ndarray:
    """``estimate(i)``: median over rows of h2_r(i)·S[r, h1_r(i)].  (items,) float32."""
    buckets, signs = _hashes(sk, key_hi, key_lo)
    gathered = jnp.take_along_axis(
        sk.table, buckets.astype(jnp.int32), axis=1)          # (R, items)
    ests = gathered.astype(jnp.float32) * signs.astype(jnp.float32)
    return jnp.median(ests, axis=0)


def merge(a: CountSketch, b: CountSketch) -> CountSketch:
    """``merge(S1, S2) = S1 + S2``.  Hash params must match (checked by shape
    only inside jit; value equality is the caller's contract, as in the paper:
    'the hashing functions and the sketch matrix sizes must be the same')."""
    return a._replace(table=a.table + b.table)


def psum_merge(sk: CountSketch, axis_name) -> CountSketch:
    """Distributed merge across a mesh axis: the collective IS the algorithm.

    ``axis_name`` may be a single name or a tuple of names; with a tuple the
    reduction is hierarchical in the mesh ordering (ICI first, DCN second)."""
    return sk._replace(table=jax.lax.psum(sk.table, axis_name))


def l2_estimate(sk: CountSketch) -> jnp.ndarray:
    """AMS-style ℓ₂ estimate: median over rows of Σ_c S[r,c]² (paper §II-3)."""
    return jnp.sqrt(jnp.median(jnp.sum(
        sk.table.astype(jnp.float32) ** 2, axis=1)))


def tensor_sketch_update(sk: CountSketch, grad_flat: jnp.ndarray
                         ) -> CountSketch:
    """Sketch a dense vector (gradient compression): coordinate i of the
    vector is 'item i' with value grad[i].  Used by optim/sketch_compress."""
    n = grad_flat.shape[0]
    idx = jnp.arange(n, dtype=jnp.uint32)
    key_hi = jnp.zeros_like(idx)
    return update(sk, key_hi, idx, values=grad_flat)


def tensor_sketch_estimate(sk: CountSketch, n: int) -> jnp.ndarray:
    """Estimate all n coordinates of a sketched dense vector.  O(n·R) gather."""
    idx = jnp.arange(n, dtype=jnp.uint32)
    return estimate(sk, jnp.zeros_like(idx), idx)


@functools.partial(jax.jit, static_argnames=("k",))
def topk_from_candidates(sk: CountSketch, cand_hi: jnp.ndarray,
                         cand_lo: jnp.ndarray, k: int,
                         cand_mask: Optional[jnp.ndarray] = None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k heavy hitters among candidate keys, by sketch estimate.

    Deduplicates candidates (same key proposed by several shards), estimates
    each on the (merged) sketch, returns (hi, lo, est) of the k largest.
    Padding/invalid candidates are masked out with -inf.
    """
    m = cand_hi.shape[0]
    order = jnp.lexsort((cand_lo, cand_hi))
    shi, slo = cand_hi[order], cand_lo[order]
    is_first = jnp.concatenate([
        jnp.ones((1,), bool),
        (shi[1:] != shi[:-1]) | (slo[1:] != slo[:-1])])
    if cand_mask is not None:
        is_first &= cand_mask[order]
    est = estimate(sk, shi, slo)
    est = jnp.where(is_first, est, -jnp.inf)
    kk = min(k, m)                      # fewer candidates than k: pad
    top_est, top_idx = jax.lax.top_k(est, kk)
    hi_out, lo_out = shi[top_idx], slo[top_idx]
    if kk < k:
        pad = k - kk
        hi_out = jnp.concatenate(
            [hi_out, jnp.full((pad,), 0xFFFFFFFF, jnp.uint32)])
        lo_out = jnp.concatenate(
            [lo_out, jnp.full((pad,), 0xFFFFFFFF, jnp.uint32)])
        top_est = jnp.concatenate([top_est, jnp.full((pad,), -jnp.inf)])
    return hi_out, lo_out, top_est
