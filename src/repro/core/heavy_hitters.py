"""Global heavy-hitter recovery: candidates × merged sketch → top-K cells.

Single-shard here; the distributed (SPMD) merge is ``geo._merge_local``,
which ends in :func:`from_candidates`.  It maps the paper's
geo-distributed topology onto a device mesh:

    per-device:  quantize → pack → local sketch update + local top-L
    data axis :  psum(sketch)           [paper: merge within a data center]
    pod axis  :  psum(sketch)           [paper: merge across data centers]
    everywhere:  all_gather(candidates) → dedupe → estimate on merged
                 sketch → global top-K   [paper: master-node HH extraction]

Every device finishes with the same top-K list (replicated), which is
*stronger* than the paper's single-master output and removes the
aggregation-site straggler.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import candidates as cand_mod
from repro.core import sketch as sketch_mod
from repro.core.candidates import Candidates
from repro.core.sketch import CountSketch


class HeavyHitters(NamedTuple):
    """Top-K cells: packed keys, estimated counts, validity mask."""
    key_hi: jnp.ndarray   # (K,) uint32
    key_lo: jnp.ndarray   # (K,) uint32
    count: jnp.ndarray    # (K,) float32 — sketch-estimated frequency
    mask: jnp.ndarray     # (K,) bool


def from_candidates(sk: CountSketch, cands: Candidates, k: int
                    ) -> HeavyHitters:
    """Dedupe candidate keys, estimate on the sketch, keep the top-k."""
    hi, lo, est = sketch_mod.topk_from_candidates(
        sk, cands.key_hi, cands.key_lo, k, cand_mask=cands.mask)
    mask = jnp.isfinite(est) & (est > 0)
    return HeavyHitters(key_hi=hi, key_lo=lo,
                        count=jnp.where(mask, est, 0.0), mask=mask)


def extract(sk: CountSketch, key_hi: jnp.ndarray, key_lo: jnp.ndarray,
            k: int, candidate_pool: Optional[int] = None,
            values: Optional[jnp.ndarray] = None,
            mask: Optional[jnp.ndarray] = None) -> HeavyHitters:
    """Single-shard convenience: exact local top-pool candidates, then
    sketch-estimated top-k (pool ≥ k; default 2k for head-room)."""
    pool = candidate_pool or min(2 * k, key_hi.shape[0])
    cands = cand_mod.local_topk(key_hi, key_lo, pool,
                                values=values, mask=mask)
    return from_candidates(sk, cands, k)


def exact_counts(key_hi: jnp.ndarray, key_lo: jnp.ndarray,
                 query_hi: jnp.ndarray, query_lo: jnp.ndarray
                 ) -> jnp.ndarray:
    """Ground-truth frequency of each query key in the stream (test oracle).
    O(items × queries) — test-scale only."""
    eq = (key_hi[None, :] == query_hi[:, None]) & \
         (key_lo[None, :] == query_lo[:, None])
    return jnp.sum(eq.astype(jnp.float32), axis=1)
