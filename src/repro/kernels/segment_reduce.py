"""Pallas kernel: fused segment reduce for the sorted-COO attraction pass.

``coo.segment_reduce`` computes per-row sums of row-sorted edge payloads
as a cumulative-sum difference — a deliberate workaround for XLA *CPU*
scatter, which walks updates serially (~100× slower at E ~ 10⁷).  On an
accelerator the cumsum trick is itself the bottleneck: it materializes an
(E+1, D) prefix array and two gathers of HBM traffic for what is really
one streaming pass over the edges.

This kernel does the reduction in ONE pass with tiled, row-bounds-aware
partial sums.  The grid runs over blocks of R output rows; each step
reads its R+1 row bounds, walks the covered edge span [bounds[r0],
bounds[r0+R]) in fixed-size chunks of C edges, and folds each chunk into
the (R, D) accumulator as a one-hot membership matmul:

    onehot[r, c] = 1  iff  bounds[r0+r] <= edge_c < bounds[r0+r+1]
    acc         += onehot @ chunk          (fp32 MXU accumulation)

Each edge chunk is read once by the single row block that owns it (plus
at most once more when a chunk straddles a block boundary), so HBM
traffic is O(E·D) — no prefix array, no gathers, no scatter.  fp32
accumulation is pinned regardless of the payload dtype.

Numerics: a direct per-row sum and the cumsum-difference reassociate
floating-point addition differently, so bitwise equality with
``coo.segment_reduce`` holds exactly when the additions are exact (e.g.
integer-valued fp32 payloads below 2²⁴ — the kernel tests pin bit-for-bit
there) and to ~1e-6 relative otherwise.

Dispatch: auto mode never picks this kernel (``prefer=never`` on both
Pallas tiers), so the cumsum path is the default on every backend until
a chip measurement shows the kernel ahead; forcing ``mode="compiled"``
or ``mode="interpret"`` still runs it.

VMEM note: v1 keeps the whole (E, D) payload and the (N+1, 1) bounds
column resident per grid step (full-array BlockSpecs, each padded to 128
lanes).  ``fits_vmem`` is the compiled tier's ``supported`` gate: shapes
whose resident windows exceed ``VMEM_BUDGET`` are refused with
``KernelUnavailableError`` instead of failing inside the TPU compiler
(at N = 4096, E = 122,880 the windows alone need ~60 MiB against a
16 MiB scoped limit).  Streaming the spans from HBM is the follow-up.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import registry


def _seg_kernel(bounds_ref, vals_ref, out_ref, *, rows: int, chunk: int):
    i = pl.program_id(0)
    r0 = i * rows
    b = bounds_ref[pl.ds(r0, rows + 1), 0]                   # (R+1,)
    lo = b[0]
    hi = b[rows]
    n_chunks = (hi - lo + chunk - 1) // chunk

    def body(j, acc):
        start = lo + j * chunk
        ch = vals_ref[pl.ds(start, chunk), :]                # (C, D)
        eidx = start + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
        inside = (eidx >= b[:rows][:, None]) & (eidx < b[1:][:, None])
        onehot = jnp.where(inside & (eidx < hi), 1.0, 0.0)   # (R, C)
        return acc + jnp.dot(onehot, ch.astype(jnp.float32),
                             preferred_element_type=jnp.float32)

    acc = jnp.zeros((rows, out_ref.shape[1]), jnp.float32)
    out_ref[...] = jax.lax.fori_loop(0, n_chunks, body, acc)


@functools.partial(
    jax.jit, static_argnames=("rows_per_block", "edge_chunk", "interpret"))
def _segment_reduce_padded(vals: jnp.ndarray, bounds2d: jnp.ndarray, *,
                           rows_per_block: int, edge_chunk: int,
                           interpret: bool) -> jnp.ndarray:
    """vals (Ep, D) f32 (guard-padded), bounds2d (Np+1, 1) int32 with Np a
    multiple of rows_per_block -> (Np, D) f32 row sums."""
    ep, d = vals.shape
    np1 = bounds2d.shape[0]
    n_pad = np1 - 1
    assert n_pad % rows_per_block == 0
    return pl.pallas_call(
        functools.partial(_seg_kernel, rows=rows_per_block,
                          chunk=edge_chunk),
        grid=(n_pad // rows_per_block,),
        in_specs=[
            pl.BlockSpec((np1, 1), lambda i: (0, 0)),
            pl.BlockSpec((ep, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rows_per_block, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), jnp.float32),
        interpret=interpret,
    )(bounds2d, vals)


def segment_reduce_pallas(vals: jnp.ndarray, bounds: jnp.ndarray, *,
                          rows_per_block: int = 128, edge_chunk: int = 256,
                          interpret: bool = True) -> jnp.ndarray:
    """Row sums of row-sorted edge payloads via the fused kernel.

    vals (E,) or (E, D); bounds (N+1,) int32 ascending with bounds[0] = 0
    and bounds[N] = E (``coo.row_bounds`` output).  Matches
    ``coo.segment_reduce`` semantics, fp32 accumulation, result cast back
    to the payload dtype.
    """
    squeeze = vals.ndim == 1
    v = vals[:, None] if squeeze else vals
    e, d = v.shape
    n = bounds.shape[0] - 1
    if n == 0:
        out = jnp.zeros((0, d), vals.dtype)
        return out[:, 0] if squeeze else out
    rows_per_block = min(rows_per_block, max(n, 1))
    n_pad = -(-n // rows_per_block) * rows_per_block
    # padded rows are empty segments: repeat the terminal bound
    bpad = jnp.concatenate(
        [bounds.astype(jnp.int32),
         jnp.full((n_pad - n,), bounds[-1], jnp.int32)])[:, None]
    # guard chunk of zero payload so the last dynamic slice never clamps
    # into live edges (dynamic_slice clamps OOB starts backwards)
    vpad = jnp.pad(v.astype(jnp.float32),
                   [(0, (-e) % edge_chunk + edge_chunk), (0, 0)])
    out = _segment_reduce_padded(
        vpad, bpad, rows_per_block=rows_per_block, edge_chunk=edge_chunk,
        interpret=interpret)[:n].astype(vals.dtype)
    return out[:, 0] if squeeze else out


def segment_reduce_xla(vals: jnp.ndarray, bounds: jnp.ndarray
                       ) -> jnp.ndarray:
    """Reference: the cumsum-difference trick (mirrors
    ``coo.segment_reduce``'s arithmetic exactly — same reassociation,
    same bits)."""
    zero = jnp.zeros((1,) + vals.shape[1:], vals.dtype)
    csum = jnp.concatenate([zero, jnp.cumsum(vals, axis=0)], axis=0)
    return csum[bounds[1:]] - csum[bounds[:-1]]


# -- registry wiring --------------------------------------------------------

#: Resident-window budget the compiled tier accepts (the v5e scoped-VMEM
#: default is 16 MiB; windows at this size compile for ``v5e``, see
#: tests/test_tpu_compile.py).
VMEM_BUDGET = 16 << 20


def vmem_bytes(n_rows: int, n_edges: int, width: int, *,
               rows_per_block: int, edge_chunk: int) -> int:
    """Bytes of the resident VMEM windows the compiled kernel needs for
    ``n_rows`` output rows and an (``n_edges``, ``width``) payload: the
    guard-padded payload plus the bounds column, each rounded to (8, 128)
    f32/int32 tiles."""
    rows = min(rows_per_block, max(n_rows, 1))
    np1 = -(-n_rows // rows) * rows + 1
    ep = n_edges + (-n_edges) % edge_chunk + edge_chunk
    lanes = 128 * -(-width // 128)
    return 4 * lanes * (-(-ep // 8) * 8) + 4 * 128 * (-(-np1 // 8) * 8)


def fits_vmem(backend, shape, dtype) -> bool:
    """Compiled-tier ``supported`` gate.  ``shape`` is the op's
    ``(n_rows, n_edges, width)`` as ``coo.segment_reduce`` reports it."""
    if not registry.accel_only(backend, shape, dtype):
        return False
    if len(shape) != 3:
        return True
    tiles = registry.tile_params("segment_reduce", backend=backend)
    return vmem_bytes(*shape, **tiles) <= VMEM_BUDGET


def _run(interpret: bool):
    def fn(vals, bounds, *, rows_per_block: int = 128,
           edge_chunk: int = 256):
        return segment_reduce_pallas(
            vals, bounds, rows_per_block=rows_per_block,
            edge_chunk=edge_chunk, interpret=interpret)
    return fn


registry.register("segment_reduce", "compiled", supported=fits_vmem,
                  prefer=registry.never)(_run(False))
registry.register("segment_reduce", "interpret",
                  prefer=registry.never)(_run(True))


@registry.register("segment_reduce", "xla")
def _xla(vals, bounds, **_tile):
    return segment_reduce_xla(vals, bounds)
