"""Candidate tracking: sorted key runs, exact local top-k, reservoir merges.

The Count Sketch table estimates *frequencies* but does not store key
*identities*.  The classic stream solution keeps a heap of candidates next
to the sketch; a heap is hostile to SPMD TPU execution, so we use the
averaging argument instead: any globally (ε,ℓ₂)-heavy key is locally heavy
on at least one shard.  Each shard therefore extracts its own exact top-L
keys, and the global stage (:mod:`repro.core.heavy_hitters`) all-gathers
the candidate keys and re-estimates them on the merged sketch.

The throughput currency of the ingest hot path is :class:`KeyRuns` — the
output of ONE lexsort + run-length-encode over a chunk's keys
(:func:`sorted_runs`).  The same runs feed both sides of the streaming
fold, and the chunk's raw keys are never sorted again:

* ``sketch.update_runs``      — the deduped scatter into the sketch table;
* :func:`merge_runs`          — the bounded reservoir merge against a
  reservoir kept key-sorted as a carried invariant: two stable
  multi-operand sorts (the merged view, then the compaction) that carry
  keys and counts as payload, so no gather and no binary search;
* :func:`topk_from_runs`      — exact local top-k (one-shot shard path).

The legacy entry points (:func:`local_topk`, :func:`merge_topk`) are thin
compositions of the runs machinery and remain the reference semantics:
``merge_runs`` holds exactly the same live (key → count) set, bit-identical
counts included, as ``merge_topk`` over the raw keys — property-tested in
tests/test_fused_ingest.py.  Only the storage ORDER differs: ``merge_topk``
returns count-descending, ``merge_runs`` key-ascending (the invariant that
lets the next merge take the reservoir as one sorted input).  Heavy-hitter
extraction canonicalizes by key, so the two orders produce bit-identical
heavy hitters.

Everything is static-shape: L is fixed, shards with fewer than L distinct
keys pad with an invalid key + mask.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


INVALID_KEY = 0xFFFFFFFF


class Candidates(NamedTuple):
    """Top-L locally-frequent keys of one shard (padded, mask-carrying).

    Two storage orders occur, by provenance:

    * :func:`local_topk` / :func:`topk_from_runs` / :func:`merge_topk`
      return count-descending order (``lax.top_k`` output order);
    * :func:`merge_runs` (and therefore the streaming reservoir) returns
      live keys ascending with all padding at the end — the key-sorted
      invariant the reservoir merge relies on.  :func:`empty` satisfies it
      trivially.

    Both orders carry identical (key, count, mask) *sets*; every consumer
    (``heavy_hitters.from_candidates``, ``all_gather`` + dedupe) is
    order-insensitive.
    """
    key_hi: jnp.ndarray    # (L,) uint32
    key_lo: jnp.ndarray    # (L,) uint32
    count: jnp.ndarray     # (L,) float32 — exact local count
    mask: jnp.ndarray      # (L,) bool — False for padding

    @property
    def capacity(self) -> int:
        """Reservoir size L (static)."""
        return self.key_hi.shape[0]

    def merge_topk(self, other: "Candidates", k: int) -> "Candidates":
        """Reservoir merge: see :func:`merge_topk`."""
        return merge_topk(self, other, k=k)


class KeyRuns(NamedTuple):
    """Run-length-encoded sorted keys of one chunk — the single-sort
    currency of the ingest hot path (see :func:`sorted_runs`).

    ``key_hi/key_lo[j]`` for j < num_runs is the j-th distinct key in
    ascending (hi, lo) order; ``count[j]`` its masked value sum; positions
    j ≥ num_runs repeat the largest sorted key with count 0 (so the arrays
    stay globally non-decreasing — required by the reservoir merge).
    """
    key_hi: jnp.ndarray    # (n,) uint32 — run keys, ascending, compacted
    key_lo: jnp.ndarray    # (n,) uint32
    count: jnp.ndarray     # (n,) summed value per run (0 past num_runs)
    live: jnp.ndarray      # (n,) bool — position < num_runs

    @property
    def size(self) -> int:
        return self.key_hi.shape[0]


def empty(k: int) -> Candidates:
    """An all-padding candidate reservoir of capacity k (merge identity;
    key-sorted trivially)."""
    return Candidates(
        key_hi=jnp.full((k,), INVALID_KEY, jnp.uint32),
        key_lo=jnp.full((k,), INVALID_KEY, jnp.uint32),
        count=jnp.zeros((k,), jnp.float32),
        mask=jnp.zeros((k,), bool))


@jax.named_scope("sns.fold.sort")
def sorted_runs(key_hi: jnp.ndarray, key_lo: jnp.ndarray,
                values: Optional[jnp.ndarray] = None,
                mask: Optional[jnp.ndarray] = None,
                dtype=jnp.float32, assume_hi_zero: bool = False) -> KeyRuns:
    """THE sort of the ingest hot path: lexsort (hi, lo) → run-length
    segments → per-run value sum.  One TPU-native bitonic sort per chunk;
    everything downstream (sketch scatter, reservoir merge, local top-k)
    consumes the runs without re-sorting.

    ``values`` defaults to 1 (counting); ``mask`` zeroes padding rows —
    masked rows still occupy sort slots, so a run whose occurrences are all
    masked survives with count 0 (dropped later by liveness filters).

    ``assume_hi_zero`` is a STATIC fast path for keys known to fit the low
    limb (grids packing ≤ 32 bits, i.e. ``dims·bits_per_dim ≤ 32`` — the
    caller's contract): the sort compares one u32 key instead of two,
    which is the dominant cost of the whole fold.  With ``key_hi ≡ 0``
    both paths are the identical stable permutation, so results are
    bit-identical.
    """
    n = key_hi.shape[0]
    v = jnp.ones((n,), dtype) if values is None else values.astype(dtype)
    if mask is not None:
        v = v * mask.astype(dtype)
    order = jnp.lexsort((key_lo,) if assume_hi_zero else (key_lo, key_hi))
    shi, slo, sv = key_hi[order], key_lo[order], v[order]
    if assume_hi_zero:
        new_run = jnp.concatenate([
            jnp.ones((1,), bool), slo[1:] != slo[:-1]])
    else:
        new_run = jnp.concatenate([
            jnp.ones((1,), bool),
            (shi[1:] != shi[:-1]) | (slo[1:] != slo[:-1])])
    run_id = jnp.cumsum(new_run) - 1
    run_sum = jax.ops.segment_sum(sv, run_id, num_segments=n)
    # representative key of each run = first occurrence (run_id is sorted,
    # so a searchsorted replaces the costlier nonzero-with-size); dead
    # slots clip to n-1, repeating the largest sorted key so the arrays
    # stay globally non-decreasing
    first_idx = jnp.clip(
        jnp.searchsorted(run_id, jnp.arange(n), side="left"), 0, n - 1)
    return KeyRuns(key_hi=shi[first_idx], key_lo=slo[first_idx],
                   count=run_sum,
                   live=jnp.arange(n) < (run_id[-1] + 1))


def topk_from_runs(runs: KeyRuns, k: int, return_dropped: bool = False):
    """Exact top-k runs by count (count-descending order, like
    :func:`local_topk`).  ``k`` may exceed the number of slots: output is
    padded to k with invalid keys + mask=False.

    ``return_dropped=True`` additionally returns the largest live count
    NOT selected (the (k+1)-th largest; 0.0 when nothing is truncated) —
    the one-shot analog of the reservoir eviction watermark: any key with
    a larger local count is guaranteed to be among the candidates."""
    n = runs.size
    live = runs.live & (runs.count > 0)
    score = jnp.where(live, runs.count.astype(jnp.float32), -jnp.inf)
    kk = min(k, n)                      # top_k(score, k) requires k <= n
    kk2 = min(k + 1, n)                 # one extra for the drop watermark
    top_score, top_idx = jax.lax.top_k(score, kk2)
    dropped = jnp.maximum(top_score[kk2 - 1], 0.0) if kk2 > kk \
        else jnp.zeros(())              # kk == n: nothing can be dropped
    top_score, top_idx = top_score[:kk], top_idx[:kk]
    cmask = jnp.isfinite(top_score)
    out = Candidates(
        key_hi=jnp.where(cmask, runs.key_hi[top_idx],
                         jnp.uint32(INVALID_KEY)),
        key_lo=jnp.where(cmask, runs.key_lo[top_idx],
                         jnp.uint32(INVALID_KEY)),
        count=jnp.where(cmask, top_score, 0.0),
        mask=cmask)
    if kk < k:                          # fewer items than the pool: pad
        out = concat(out, empty(k - kk))
    if return_dropped:
        return out, dropped
    return out


def local_topk(key_hi: jnp.ndarray, key_lo: jnp.ndarray, k: int,
               values: Optional[jnp.ndarray] = None,
               mask: Optional[jnp.ndarray] = None) -> Candidates:
    """Exact top-k distinct keys of this shard by total count/value:
    :func:`sorted_runs` + :func:`topk_from_runs`.  O(n log n) work, fully
    vectorized, static shapes."""
    return topk_from_runs(
        sorted_runs(key_hi, key_lo, values=values, mask=mask), k)


def concat(*cands: Candidates) -> Candidates:
    """Concatenate candidate sets (e.g. after all_gather over shards)."""
    return Candidates(
        key_hi=jnp.concatenate([c.key_hi for c in cands]),
        key_lo=jnp.concatenate([c.key_lo for c in cands]),
        count=jnp.concatenate([c.count for c in cands]),
        mask=jnp.concatenate([c.mask for c in cands]))


def runs_from_candidates(c: Candidates) -> KeyRuns:
    """View a candidate set (DISTINCT keys — a reservoir or a top-k, any
    storage order) as :class:`KeyRuns`, the currency :func:`merge_runs`
    consumes: one lexsort puts live keys ascending (INVALID padding sorts
    last, count forced to 0), satisfying the globally-non-decreasing
    contract.  This is what lets two *reservoirs* merge through the same
    path as the streaming fold — the host-level mergeability
    behind ``stream.merge_states`` / partial aggregation."""
    order = jnp.lexsort((c.key_lo, c.key_hi))
    return KeyRuns(
        key_hi=c.key_hi[order],
        key_lo=c.key_lo[order],
        count=jnp.where(c.mask, c.count, 0.0)[order].astype(jnp.float32),
        live=c.mask[order])


def merge_topk(a: Candidates, b: Candidates, k: int) -> Candidates:
    """Unordered reservoir merge: concat → lexsort → dedupe (sum counts of
    equal keys) → exact top-k.  Works for ANY input order (the all-gather
    merge path); the streaming fold uses :func:`merge_runs` on its
    key-sorted reservoir instead, which holds the identical live set.  A
    key held by either side keeps its full accumulated count, so as long as the number of distinct
    keys ever seen stays ≤ k the reservoir equals the exact top-k of the
    whole stream."""
    c = concat(a, b)
    return local_topk(c.key_hi, c.key_lo, k, values=c.count, mask=c.mask)


@jax.named_scope("sns.fold.merge")
def merge_runs(pool: Candidates, runs: KeyRuns, k: int
               ) -> Tuple[Candidates, jnp.ndarray]:
    """Bounded reservoir merge on two stable sorts: the streaming-fold
    hot path.

    ``pool`` MUST be key-sorted (live keys ascending, padding at the end —
    the invariant :func:`empty` starts and this function maintains); the
    chunk side arrives pre-deduped and sorted as :class:`KeyRuns`.  Both
    sides are then globally non-decreasing, and the merge is built from
    two stable multi-operand ``lax.sort`` calls that carry the payload as
    operands, plus elementwise ops, cumsums and reductions — no gather,
    no binary search (on a TPU v5e a sort of 2^20 elements costs
    ~0.8 ms, while the gathers of a searchsorted merge of a 40,000-slot
    reservoir with a 65,536-key chunk cost ~37 ms):

    1. the merged key-sorted view is one stable sort of pool ++ runs on
       (hi, lo) with the count as payload; stability puts the pool entry
       first on equal keys, pool padding (INVALID, INVALID) sorts last
       and a run's dead slots (largest run key, count 0) follow its last
       live run;
    2. duplicate keys sum by a shifted pair-add: the pool holds distinct
       keys and the runs are deduped, so every merged key has ≤ 2 nonzero
       occurrences, adjacent, pool first — no segment_sum;
    3. the k-th largest count comes from a bitwise bisection on the
       (monotone for finite non-negatives) float32 bit pattern, counting
       survivors per trial bit — no top_k;
    4. selected run heads compact to the front by a stable sort on the
       0/1 "not selected" key — stability keeps them in ascending key
       order, so the key-sorted invariant is preserved.

    Bit-identity with :func:`merge_topk`: identical live keys and exactly
    equal counts (all adds are exact small integers in f32; the selection
    reproduces ``lax.top_k``'s break-ties-by-lower-index rule, which in
    both paths means ascending key order).

    Returns ``(merged, evicted_max)`` where ``evicted_max`` is the largest
    count evicted in THIS merge (0.0 if nothing was evicted) — the
    space-saving diagnostic accumulated by ``stream.IngestState``.
    """
    if k > pool.capacity + runs.size:
        raise ValueError(f"k={k} exceeds pool + runs "
                         f"({pool.capacity} + {runs.size})")
    p_cnt = pool.count * pool.mask.astype(pool.count.dtype)
    r_cnt = runs.count.astype(jnp.float32)

    # 1. merged view: stable two-key sort of pool ++ runs (pool first)
    m_hi, m_lo, m_cnt = jax.lax.sort(
        (jnp.concatenate([pool.key_hi, runs.key_hi]),
         jnp.concatenate([pool.key_lo, runs.key_lo]),
         jnp.concatenate([p_cnt, r_cnt])), num_keys=2, is_stable=True)

    # 2. pair-add dedupe: each key occurs ≤ 2× with nonzero count (pool
    # distinct ∧ runs deduped), adjacent, pool first — the sum of a run
    # is its head count plus its immediate same-key successor's
    new_run = jnp.concatenate([
        jnp.ones((1,), bool),
        (m_hi[1:] != m_hi[:-1]) | (m_lo[1:] != m_lo[:-1])])
    nxt_cnt = jnp.concatenate([m_cnt[1:], jnp.zeros((1,), jnp.float32)])
    csum = m_cnt + jnp.where(jnp.concatenate([~new_run[1:],
                                              jnp.zeros((1,), bool)]),
                             nxt_cnt, 0.0)
    live = new_run & (csum > 0)       # value valid at run heads only

    # 3. k-th largest live count: bitwise-greedy max threshold t with
    # |{live : count ≥ t}| ≥ k, on the f32 bit pattern (monotone for
    # finite non-negative floats); t = 0 when fewer than k live
    cbits = jax.lax.bitcast_convert_type(csum, jnp.uint32)
    thresh = jnp.zeros((), jnp.uint32)
    for b in range(30, -1, -1):       # counts are finite positives: ≤ 2³¹
        cand = thresh | jnp.uint32(1 << b)
        cnt = jnp.sum(live & (cbits >= cand))
        thresh = jnp.where(cnt >= k, cand, thresh)
    gt = live & (cbits > thresh)
    n_gt = jnp.sum(gt.astype(jnp.int32))
    eq = live & (cbits == thresh) & (csum > 0)
    eq_rank = jnp.cumsum(eq) - 1
    sel = gt | (eq & (eq_rank < (k - n_gt)))

    evicted = jnp.where(live & ~sel, csum, 0.0)
    evicted_max = jnp.max(evicted, initial=0.0)

    # 4. compaction: a stable sort on the 0/1 "not selected" key moves the
    # selected heads to the front in their merged (ascending key) order
    _, s_hi, s_lo, s_cnt = jax.lax.sort(
        ((~sel).astype(jnp.int32), m_hi, m_lo, csum),
        num_keys=1, is_stable=True)
    valid = jnp.arange(k) < jnp.sum(sel.astype(jnp.int32))
    out = Candidates(
        key_hi=jnp.where(valid, s_hi[:k], jnp.uint32(INVALID_KEY)),
        key_lo=jnp.where(valid, s_lo[:k], jnp.uint32(INVALID_KEY)),
        count=jnp.where(valid, s_cnt[:k], 0.0),
        mask=valid)
    return out, evicted_max


def all_gather(cands: Candidates, axis_name) -> Candidates:
    """Gather every shard's candidates along a mesh axis -> (shards*L,) sets."""
    gathered = jax.lax.all_gather(cands, axis_name, tiled=True)
    return gathered
