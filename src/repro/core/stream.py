"""Streaming ingest engine: bounded-memory, single-sort, superbatched.

The paper's headline resource claim (§II) is *logarithmic memory* and
*single-stream I/O* on the edge nodes; its headline time claim is *linear*
at 10⁸⁺ points — which makes the ingest front-end a points/sec throughput
engine, not just a memory bound.  This module provides the fold:

    ``IngestState``      = CountSketch ⊕ key-sorted Candidates reservoir
                           ⊕ count ⊕ eviction watermark
    ``ingest_step``      : state × (chunk, mask) → state      (traceable)
    ``ingest_chunk``     : jitted, donated single-chunk wrapper
    ``ingest_superbatch``: jitted, donated ``lax.scan`` over B stacked
                           chunks — one dispatch amortizes B steps
    ``ingest_all``       : host driver — rechunk → superbatch → double-
                           buffered async prefetch (device_put of batch
                           b+1 overlaps the compute of batch b)

Hot-path structure (the fused single-sort fold): ``ingest_step`` sorts and
run-length-encodes the chunk's keys ONCE (``candidates.sorted_runs``) and
feeds the same deduped runs to both consumers — the sketch scatter
(``sketch.update_runs``) and the reservoir merge
(``candidates.merge_runs``, two stable multi-operand sorts of the
key-sorted reservoir with the runs, no gather).  One sort of the chunk's
keys and two in the merge per chunk step, jaxpr-regression-tested in
tests/test_fused_ingest.py.  The four stages carry a ``jax.named_scope``
in the functions that do the work, so every caller's compiled ops are
named by stage in their ``op_name``:
``sns.fold.keys`` (``quantize.points_to_keys``), ``sns.fold.sort``
(``candidates.sorted_runs``), ``sns.fold.scatter`` (``sketch.update_runs``)
and ``sns.fold.merge`` (``candidates.merge_runs``, also under
:func:`merge_states`).

The reservoir invariant: a key held by the reservoir accumulates its
*exact* count, so while the number of distinct keys seen stays ≤ L the
reservoir is bit-identical to the one-shot exact top-L of the concatenated
stream — the equivalence contract tested in tests/test_stream_ingest.py.
Beyond L distinct keys it degrades to a space-saving-style approximation;
``state.evict_max`` tracks the running maximum count ever evicted, the
space-saving error diagnostic surfaced by the pipeline (a key whose true
count exceeds every eviction it suffered survives; see
:func:`space_saving_bound`).

``save_state`` / ``load_state`` checkpoint the fold mid-stream (resumable
ingest): the state is a flat pytree of arrays, round-tripped through one
``.npz`` — resuming reproduces bit-identical heavy hitters.  Writes are
ATOMIC (temp file + ``os.replace``: a crash mid-save can never leave a
torn file at the target path) and CHECKSUMMED (a crc32 digest rides in
the payload; ``load_state`` recomputes it and raises
:class:`CheckpointCorruptError` on silent bit rot, optionally falling
back to the previous good generation written by ``keep_backup=True``).

``merge_states`` is the host-level mergeability primitive: two folds
built with identical hash params combine linearly (sketch tables add,
reservoirs sorted-merge, counts add, eviction watermarks max) — the
partial-aggregation backbone of ``core.resilience``.

Used by the single-host streaming pipeline (``pipeline.run_streaming``)
and, via ``ingest_step`` inside ``lax.scan``, by the mesh paths: the
sites of ``SnsService`` (``geo.fold_sites``, fed by :func:`ingest_sites`)
and ``geo.geo_extract_from_shards``.
"""
from __future__ import annotations

import functools
import os
import zlib
from typing import Iterable, Iterator, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import candidates as cand_mod
from repro.core import hashing, quantize, sketch as sketch_mod
from repro.core.candidates import Candidates
from repro.core.quantize import GridSpec
from repro.core.sketch import CountSketch


class IngestState(NamedTuple):
    """Everything the sketch stage carries between chunks.  A pytree, so it
    scans, donates, and psums like any other JAX state.

    ``cands`` is maintained KEY-SORTED (live keys ascending, padding last)
    — the invariant that lets ``candidates.merge_runs`` take it as one
    sorted input of its merge.  ``evict_max`` is the space-saving
    diagnostic: the largest exact count ever evicted from the reservoir (0
    while the distinct-key universe fits in the pool, i.e. while the
    reservoir is exact)."""
    sketch: CountSketch     # (R, C) table + hash params
    cands: Candidates       # (L,) bounded candidate reservoir, key-sorted
    count: jnp.ndarray      # () float32 — items ingested so far
    evict_max: jnp.ndarray  # () float32 — running max evicted count


def init(key: jax.Array, rows: int, log2_cols: int, pool: int,
         dtype=jnp.float32) -> IngestState:
    """Fresh state: zero sketch, empty reservoir of capacity ``pool``."""
    return IngestState(
        sketch=sketch_mod.init(key, rows, log2_cols, dtype=dtype),
        cands=cand_mod.empty(pool),
        count=jnp.zeros((), jnp.float32),
        evict_max=jnp.zeros((), jnp.float32))


def from_sketch(sk: CountSketch, pool: int) -> IngestState:
    """Wrap an existing (e.g. replicated-into-shard_map) sketch."""
    return IngestState(sketch=sk, cands=cand_mod.empty(pool),
                       count=jnp.zeros((), jnp.float32),
                       evict_max=jnp.zeros((), jnp.float32))


def space_saving_bound(state: IngestState) -> jnp.ndarray:
    """Error bound on heavy-hitter *recall* from the reservoir: any key
    whose exact stream count exceeds ``evict_max`` at every eviction it
    suffered is still in the reservoir; 0 means the reservoir is exact
    (no eviction ever happened).  Reported counts themselves come from the
    sketch estimate and are not affected."""
    return state.evict_max


def ingest_step(state: IngestState, grid: GridSpec, points: jnp.ndarray,
                mask: Optional[jnp.ndarray] = None) -> IngestState:
    """Traceable fused fold of one chunk: quantize → pack → ONE sort+RLE →
    {sketch scatter, sorted-merge reservoir update}.  Call inside
    ``lax.scan`` / ``shard_map`` / jit.

    The chunk's deduped runs enter the reservoir merge directly — no
    per-chunk top-L truncation (a chunk with more than ``pool`` distinct
    keys loses nothing here; eviction happens only at the reservoir
    boundary, where it raises the ``evict_max`` watermark)."""
    pool = state.cands.capacity
    n = points.shape[0]
    key_hi, key_lo = quantize.points_to_keys(grid, points)
    # grids packing ≤ 32 bits leave key_hi ≡ 0 — sort one limb (static)
    hi_zero = grid.dims * grid.bits_per_dim <= 32
    runs = cand_mod.sorted_runs(key_hi, key_lo, mask=mask,
                                assume_hi_zero=hi_zero)       # THE sort
    sk = sketch_mod.update_runs(state.sketch, runs)
    cands, evicted = cand_mod.merge_runs(state.cands, runs, pool)
    if mask is None:
        inc = jnp.full((), n, jnp.float32)
    else:
        inc = jnp.sum(mask.astype(jnp.float32))
    return IngestState(sketch=sk, cands=cands, count=state.count + inc,
                       evict_max=jnp.maximum(state.evict_max, evicted))


@functools.partial(jax.jit, static_argnames=("grid",), donate_argnums=(0,))
def ingest_chunk(state: IngestState, points: jnp.ndarray,
                 mask: jnp.ndarray, *, grid: GridSpec) -> IngestState:
    """Jitted single-trace ingest step.  ``state`` is donated: the sketch
    table and reservoir are updated in place, so steady-state device memory
    is one state + one chunk.  Feed fixed-shape (points, mask) blocks —
    :func:`rechunk` produces them from any ragged iterator."""
    return ingest_step(state, grid, points, mask=mask)


@functools.partial(jax.jit, static_argnames=("grid",), donate_argnums=(0,))
def ingest_superbatch(state: IngestState, points: jnp.ndarray,
                      mask: jnp.ndarray, *, grid: GridSpec) -> IngestState:
    """Jitted fold of B stacked chunks in ONE dispatch: ``points`` is
    (B, chunk, D), ``mask`` (B, chunk).  A ``lax.scan`` over the leading
    axis carries the donated state, so trace size and per-call memory are
    O(1) in B while the Python-loop/dispatch overhead is paid once per
    superbatch instead of once per chunk.  Fully-masked chunks are
    no-ops — the host driver pads the ragged tail superbatch with them."""
    return fold_superbatch(state, grid, points, mask)


def fold_superbatch(state: IngestState, grid: GridSpec, points: jnp.ndarray,
                    mask: jnp.ndarray) -> IngestState:
    """Traceable body of :func:`ingest_superbatch`: ``ingest_step`` in a
    ``lax.scan`` over the leading axis of (B, chunk, D) points and (B,
    chunk) masks.  Also one site's fold under ``geo.fold_sites``."""
    def step(st, batch):
        pts, m = batch
        return ingest_step(st, grid, pts, mask=m), ()

    state, _ = jax.lax.scan(step, state, (points, mask))
    return state


Chunk = Union[np.ndarray, jnp.ndarray]


def rechunk(chunks: Iterable[Chunk], size: int,
            ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """Repack a ragged stream of (n_i, D) arrays into fixed (size, D)
    blocks + boolean masks (padding rows are zeros, mask=False).  Order
    preserving; host-side; O(size) working memory."""
    buf: list = []
    have = 0
    dims = None
    for c in chunks:
        c = np.asarray(c, np.float32)
        if c.ndim != 2:
            c = c.reshape(-1, c.shape[-1])
        if dims is None:
            dims = c.shape[1]
        while c.shape[0] > 0:
            take = min(size - have, c.shape[0])
            buf.append(c[:take])
            have += take
            c = c[take:]
            if have == size:
                yield (np.concatenate(buf, axis=0),
                       np.ones((size,), bool))
                buf, have = [], 0
    if have > 0:
        pts = np.concatenate(buf, axis=0)
        pad = size - have
        pts = np.concatenate(
            [pts, np.zeros((pad, dims), np.float32)], axis=0)
        mask = np.arange(size) < have
        yield pts, mask


def _superbatches(blocks: Iterator[Tuple[np.ndarray, np.ndarray]],
                  b: int) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """Stack b fixed-shape (chunk, D) blocks into (b, chunk, D) + (b, chunk)
    superbatches; the ragged tail is padded with fully-masked chunks so
    every superbatch has the same shape (exactly one trace)."""
    buf_p, buf_m = [], []
    for pts, mask in blocks:
        buf_p.append(pts)
        buf_m.append(mask)
        if len(buf_p) == b:
            yield np.stack(buf_p), np.stack(buf_m)
            buf_p, buf_m = [], []
    if buf_p:
        pad_p = np.zeros_like(buf_p[0])
        pad_m = np.zeros_like(buf_m[0])
        while len(buf_p) < b:
            buf_p.append(pad_p)
            buf_m.append(pad_m)
        yield np.stack(buf_p), np.stack(buf_m)


def ingest_all(state: IngestState, grid: GridSpec,
               chunks: Iterable[Chunk], chunk_size: int,
               superbatch: int = 1) -> IngestState:
    """Drive the jitted fold over a whole (host-side) chunk stream.

    ``superbatch`` > 1 enables the throughput path: B rechunked blocks are
    stacked per dispatch (:func:`ingest_superbatch`); ``superbatch=1``
    folds one chunk per dispatch (:func:`ingest_chunk`).  Either way the
    host→device transfer of batch b+1 is enqueued while b computes — JAX
    dispatch is asynchronous, so ``device_put`` of the next batch overlaps
    the running fold (double buffering).

    Host spans (``repro.obs``): ``sns.ingest.assemble`` (rechunk and
    stack one batch), ``sns.ingest.put`` (its ``device_put``),
    ``sns.ingest.dispatch`` (the fold's call; a long one compiles).
    Counters: ``sns.ingest.points`` (valid rows), ``sns.ingest.rows``
    (rows dispatched, padding included), ``sns.ingest.superbatches``
    (dispatches; a lone chunk when ``superbatch`` is 1) and
    ``sns.ingest.put_bytes``."""
    if superbatch <= 1:
        batches = rechunk(chunks, chunk_size)
        fold = ingest_chunk
    else:
        batches = _superbatches(rechunk(chunks, chunk_size), superbatch)
        fold = ingest_superbatch
    return _drive(state, batches,
                  lambda st, pts, mask: fold(st, pts, mask, grid=grid),
                  lambda pts, mask: (jax.device_put(pts),
                                     jax.device_put(mask)))


def _drive(state: IngestState, batches, fold, put) -> IngestState:
    """The double-buffered host loop of :func:`ingest_all` and
    :func:`ingest_sites`: take the next (points, mask) block of
    ``batches``, ``put`` it on the device(s), ``fold`` it into the state;
    the fold is dispatched asynchronously, so assembling and putting the
    next block overlaps the running one.  Spans and counters as
    documented in :func:`ingest_all`."""
    def _next():
        with obs.span("sns.ingest.assemble"):
            batch = next(batches, None)
        if batch is None:
            return None
        pts, mask = batch
        with obs.span("sns.ingest.put"):
            out = put(pts, mask)
        obs.count("sns.ingest.put_bytes", pts.nbytes + mask.nbytes)
        return out + (int(np.count_nonzero(mask)), mask.size)

    nxt = _next()
    while nxt is not None:
        pts, mask, points, rows = nxt
        with obs.span("sns.ingest.dispatch"):
            state = fold(state, pts, mask)
        obs.count("sns.ingest.points", points)
        obs.count("sns.ingest.rows", rows)
        obs.count("sns.ingest.superbatches")
        # state is dispatched asynchronously — assembling + transferring
        # the next batch here overlaps the device-side compute
        nxt = _next()
    return state


def ingest_sites(state: IngestState, site_chunks, chunk_size: int,
                 superbatch: int, fold, sharding) -> IngestState:
    """Drive a sharded fold over one chunk stream per site.

    ``state`` holds every site's fold with a leading (sites,) axis;
    ``site_chunks`` gives one host-side chunk source per site, each
    rechunked and stacked as :func:`ingest_all` does.  Each dispatch
    stacks the sites' next superbatches into one (sites, B, chunk, D)
    block and its (sites, B, chunk) mask, and puts both with one
    ``device_put`` against ``sharding``, so each site's rows go straight
    to the device that holds its fold; ``fold(state, points, mask)``
    folds every site in one dispatch.  A site whose stream ends first is
    fed fully-masked (no-op) blocks.  The next block is assembled and
    put while the current one folds (the same double buffering).

    Host spans and counters as in :func:`ingest_all`, counting the rows
    of every site."""
    feeds = [_superbatches(rechunk(c, chunk_size), superbatch)
             for c in site_chunks]
    return _drive(state, _stacked(feeds), fold,
                  lambda pts, mask: jax.device_put((pts, mask), sharding))


def _stacked(feeds) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """(sites, B, chunk, D) + (sites, B, chunk) blocks of every site's
    next superbatch, until every feed ends; a feed that ended gives
    fully-masked blocks meanwhile."""
    while True:
        blocks = [next(f, None) for f in feeds]
        live = [b for b in blocks if b is not None]
        if not live:
            return
        pad = (np.zeros_like(live[0][0]), np.zeros_like(live[0][1]))
        blocks = [pad if b is None else b for b in blocks]
        yield (np.stack([b[0] for b in blocks]),
               np.stack([b[1] for b in blocks]))


def merge_states(a: IngestState, b: IngestState) -> IngestState:
    """Linear merge of two ingest folds built with IDENTICAL hash params
    (the paper's same-hash-functions contract — checked by table shape;
    value equality is the caller's responsibility, exactly as in
    ``sketch.merge``): sketch tables add, candidate reservoirs combine
    through the streaming fold's merge (``b``'s reservoir re-keyed as
    runs via ``candidates.runs_from_candidates``), counts add, and the
    eviction watermarks max — including anything evicted by THIS merge,
    so the space-saving diagnostic stays a true upper bound.

    This is the host-level aggregation primitive: what ``psum`` does
    inside ``shard_map``, done between independently-built shard states —
    the backbone of partial aggregation (``resilience.collect_shards``),
    where exactly the shards that delivered are merged and the rest are
    accounted as lost mass."""
    if a.sketch.table.shape != b.sketch.table.shape:
        raise ValueError(
            f"cannot merge sketches of different geometry: "
            f"{a.sketch.table.shape} vs {b.sketch.table.shape}")
    # host-side states (device_get'd shard results, loaded checkpoints)
    # arrive as numpy: lift every leaf to a device array, so the merge
    # below runs the same jnp code on the same inputs as the fold
    a = jax.tree_util.tree_map(jnp.asarray, a)
    b = jax.tree_util.tree_map(jnp.asarray, b)
    runs = cand_mod.runs_from_candidates(b.cands)
    cands, evicted = cand_mod.merge_runs(a.cands, runs, a.cands.capacity)
    return IngestState(
        sketch=sketch_mod.merge(a.sketch, b.sketch),
        cands=cands,
        count=a.count + b.count,
        evict_max=jnp.maximum(jnp.maximum(a.evict_max, b.evict_max),
                              evicted))


class CheckpointCorruptError(RuntimeError):
    """A checkpoint failed to parse or failed its checksum."""


def _npz_path(path) -> str:
    """np.savez appends '.npz' to suffix-less paths but np.load does not —
    normalize so save/load accept the same path string."""
    path = os.fspath(path)
    return path if path.endswith(".npz") else path + ".npz"


def backup_path(path) -> str:
    """The previous-good-generation file ``save_state(keep_backup=True)``
    rotates to (``<path>.npz.bak``)."""
    return _npz_path(path) + ".bak"


def _payload_crc(payload: dict) -> int:
    """crc32 over (name, bytes) of every array, in sorted-name order —
    the integrity digest stored inside the checkpoint itself."""
    crc = 0
    for k in sorted(payload):
        if k == "checksum_crc32":
            continue
        crc = zlib.crc32(k.encode(), crc)
        crc = zlib.crc32(np.ascontiguousarray(payload[k]).tobytes(), crc)
    return crc & 0xFFFFFFFF


def state_digest(state: IngestState) -> int:
    """crc32 fingerprint of a fold's arrays — computed at the source,
    verified on arrival (``resilience.collect_shards(verify=True)``), so
    a bit flipped in transit is detected instead of silently merged."""
    crc = 0
    for leaf in jax.tree_util.tree_leaves(state):
        crc = zlib.crc32(
            np.ascontiguousarray(np.asarray(leaf)).tobytes(), crc)
    return crc & 0xFFFFFFFF


def save_state(state: IngestState, path, extra=None,
               keep_backup: bool = False) -> None:
    """Checkpoint the ingest fold mid-stream to one ``.npz`` (resumable
    ingest; a missing ``.npz`` suffix is added).  Everything the fold
    carries — sketch table, hash params, reservoir, count, eviction
    watermark — round-trips exactly, so resuming reproduces bit-identical
    heavy hitters.

    Crash safety: the payload is written to a temp file in the target
    directory and moved into place with ``os.replace`` — readers see the
    old complete file or the new complete file, never a torn one.  A
    crc32 over every array travels inside the payload; ``load_state``
    verifies it.  ``keep_backup=True`` first rotates an existing
    checkpoint to :func:`backup_path` — the previous good generation
    ``load_state(fallback=True)`` falls back to.

    ``extra`` (optional str → array mapping) rides along under
    ``extra_``-prefixed keys — how the service persists its embed cache
    next to the fold without a second file."""
    payload = dict(
        table=np.asarray(state.sketch.table),
        hash_params=np.stack([np.asarray(p) for p in state.sketch.params]),
        cand_key_hi=np.asarray(state.cands.key_hi),
        cand_key_lo=np.asarray(state.cands.key_lo),
        cand_count=np.asarray(state.cands.count),
        cand_mask=np.asarray(state.cands.mask),
        count=np.asarray(state.count),
        evict_max=np.asarray(state.evict_max))
    for k, v in (extra or {}).items():
        if not k or not isinstance(k, str):
            raise ValueError(f"extra keys must be non-empty strings; "
                             f"got {k!r}")
        payload["extra_" + k] = np.asarray(v)
    payload["checksum_crc32"] = np.uint32(_payload_crc(payload))
    target = _npz_path(path)
    tmp = target + f".tmp.{os.getpid()}"
    try:
        # savez on an OPEN FILE OBJECT never appends a suffix, so the
        # temp name is exactly what os.replace moves
        with open(tmp, "wb") as f:
            np.savez(f, **payload)
        if keep_backup and os.path.exists(target):
            os.replace(target, backup_path(path))
        os.replace(tmp, target)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _load_npz(p: str, with_extra: bool):
    """One checkpoint file → state (+extras), verifying the checksum.
    Raises :class:`CheckpointCorruptError` on ANY parse or digest
    failure — a torn zip, a missing field, a flipped bit."""
    try:
        with np.load(p) as z:
            arrays = {k: z[k] for k in z.files}
    except Exception as e:                               # noqa: BLE001
        raise CheckpointCorruptError(
            f"checkpoint {p!r} unreadable: {type(e).__name__}: {e}") from e
    stored = arrays.pop("checksum_crc32", None)
    if stored is not None and int(stored) != _payload_crc(arrays):
        raise CheckpointCorruptError(
            f"checkpoint {p!r} failed its crc32 check (bit rot or a "
            f"partial overwrite)")
    try:
        params = hashing.MulShiftParams(
            *(jnp.asarray(arrays["hash_params"][i]) for i in range(6)))
        state = IngestState(
            sketch=CountSketch(table=jnp.asarray(arrays["table"]),
                               params=params),
            cands=Candidates(
                key_hi=jnp.asarray(arrays["cand_key_hi"]),
                key_lo=jnp.asarray(arrays["cand_key_lo"]),
                count=jnp.asarray(arrays["cand_count"]),
                mask=jnp.asarray(arrays["cand_mask"])),
            count=jnp.asarray(arrays["count"]),
            evict_max=jnp.asarray(arrays["evict_max"]))
    except (KeyError, IndexError, ValueError) as e:
        raise CheckpointCorruptError(
            f"checkpoint {p!r} missing/malformed fields: {e}") from e
    if not with_extra:
        return state
    extras = {k[len("extra_"):]: arrays[k] for k in arrays
              if k.startswith("extra_")}
    return state, extras


def load_state(path, with_extra: bool = False, fallback: bool = False):
    """Inverse of :func:`save_state`.  With ``with_extra=True`` returns
    ``(state, extras)`` where extras maps the un-prefixed ``extra=`` keys
    saved alongside (empty dict if none).

    Integrity: the stored crc32 is recomputed over every array —
    mismatch, torn file, or missing fields raise
    :class:`CheckpointCorruptError` (checkpoints predating the checksum
    load without verification).  ``fallback=True`` then tries the
    previous good generation at :func:`backup_path` before giving up —
    the crash-safe pairing of ``save_state(keep_backup=True)``."""
    tried = [_npz_path(path)]
    if fallback:
        tried.append(backup_path(path))
    errors = []
    for p in tried:
        if not os.path.exists(p):
            errors.append(f"{p!r}: not found")
            continue
        try:
            return _load_npz(p, with_extra)
        except CheckpointCorruptError as e:
            errors.append(str(e))
    raise CheckpointCorruptError(
        "no loadable checkpoint: " + "; ".join(errors))
