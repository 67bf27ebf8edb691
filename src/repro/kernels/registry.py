"""Backend-aware kernel dispatch registry.

Every Pallas call site in the repo (CIC splat/gather, the kNN distance
scan, the fused tSNE force tile, the sorted-COO segment reduce) routes
through this module instead of hard-coding ``interpret = backend != "tpu"``
at each call.  Each op registers up to three implementations:

    "compiled"   — pl.pallas_call with interpret=False (Mosaic / Triton);
                   only *supported* on accelerator backends.
    "interpret"  — the same kernel body executed by the Pallas
                   interpreter; runs anywhere, bit-compatible with
                   compiled modulo fp reassociation.
    "xla"        — a pure-jnp reference with identical semantics; the
                   ground truth every other mode is tested against.

Resolution order under ``mode="auto"`` is compiled → interpret → xla:
the first implementation whose ``prefer`` predicate accepts the current
``(backend, shape, dtype)`` wins.  ``prefer`` is the *auto-ordering*
preference (e.g. the segment-reduce kernel never volunteers in auto
mode, so the cumsum-difference XLA path stays the default everywhere),
while ``supported`` is the hard capability gate (compiled kernels cannot
run on CPU at all, and a compiled kernel whose VMEM working set does not
fit refuses the shape, so forcing ``mode="compiled"`` there fails loudly
rather than silently falling back — a CI box must never *think* it
exercised Mosaic).  On an accelerator backend auto mode never picks the
interpret tier: a compiled tier that declines falls through to the XLA
reference, and the Pallas interpreter runs on a chip only when forced.

Mode precedence, highest first:

    1. an explicit ``mode=`` argument at the call site (tests pin these);
    2. a per-op override installed with :func:`set_mode_override`;
    3. the process-wide ``SNS_KERNEL_MODE`` env var (the CI kernel-matrix
       step pins ``interpret`` / ``xla`` this way, per whole process, so
       jit caches are never invalidated mid-run);
    4. ``"auto"``.

Call sites thread the resolved mode as a jit-static string, so two modes
never share a compilation cache entry.  ``SnsConfig.kernel_mode`` feeds
(1) through the config plumbing in ``core.pipeline``.

The module also owns the per-backend tile-size table (VMEM-conscious
defaults for compiled grids).  The program reads tile sizes from that
committed table only; an empirical autotune pass
(``bench_kernels --autotune OUT.json``) writes its winners to an
explicit JSON file keyed by ``(backend, op, shape-bucket)``, which
:func:`tile_params` consults only when handed that path.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
from typing import Any, Callable, Dict, Optional, Tuple

import jax

MODES = ("compiled", "interpret", "xla")
ENV_VAR = "SNS_KERNEL_MODE"

#: Backends on which a non-interpret pallas_call can actually compile.
ACCELERATOR_BACKENDS = ("tpu", "gpu", "cuda", "rocm")

Predicate = Callable[[Optional[str], Tuple[int, ...], Any], bool]


class KernelUnavailableError(RuntimeError):
    """No registered implementation satisfies the requested mode/backend."""


def always(backend: Optional[str], shape: Tuple[int, ...],
           dtype: Any) -> bool:
    """Predicate: runs anywhere."""
    return True


def accel_only(backend: Optional[str], shape: Tuple[int, ...],
               dtype: Any) -> bool:
    """Predicate: accelerator backends only (no CPU Mosaic/Triton)."""
    return backend in ACCELERATOR_BACKENDS


def never(backend: Optional[str], shape: Tuple[int, ...],
          dtype: Any) -> bool:
    """Preference: never chosen by auto mode (forced modes only)."""
    return False


@dataclasses.dataclass(frozen=True)
class KernelImpl:
    """One registered implementation of one op."""
    op: str
    mode: str            # "compiled" | "interpret" | "xla"
    fn: Callable
    supported: Predicate  # hard capability gate (checked even when forced)
    prefer: Predicate     # auto-ordering preference (checked in "auto" only)

    def __call__(self, *args, **kwargs):
        return self.fn(*args, **kwargs)


_REGISTRY: Dict[str, Dict[str, KernelImpl]] = {}
_MODE_OVERRIDES: Dict[str, str] = {}   # op (or "*") -> mode
_LOCK = threading.Lock()
_BUILTINS_LOADED = False


def register(op: str, mode: str, *, supported: Predicate = None,
             prefer: Predicate = None) -> Callable[[Callable], Callable]:
    """Decorator: register ``fn`` as the ``mode`` implementation of ``op``.

    ``supported`` defaults to :func:`always` for interpret/xla and
    :func:`accel_only` for compiled; ``prefer`` defaults to ``supported``.
    Re-registering an (op, mode) pair overwrites (last wins) so tests can
    install probes.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")

    def deco(fn: Callable) -> Callable:
        sup = supported if supported is not None else (
            accel_only if mode == "compiled" else always)
        pref = prefer if prefer is not None else sup
        with _LOCK:
            _REGISTRY.setdefault(op, {})[mode] = KernelImpl(
                op=op, mode=mode, fn=fn, supported=sup, prefer=pref)
        return fn
    return deco


def _ensure_builtins() -> None:
    """Import the kernel modules whose import side-effect is registration.

    Lazy so that ``import repro.kernels.registry`` stays cheap and free of
    import cycles (the kernel modules import this module at top level).
    """
    global _BUILTINS_LOADED
    if _BUILTINS_LOADED:
        return
    _BUILTINS_LOADED = True
    from repro.kernels import cic, knn_tile, segment_reduce, tsne_forces  # noqa: F401


def list_ops() -> Tuple[str, ...]:
    _ensure_builtins()
    return tuple(sorted(_REGISTRY))


def modes_of(op: str) -> Tuple[str, ...]:
    _ensure_builtins()
    return tuple(m for m in MODES if m in _REGISTRY.get(op, {}))


def get(op: str, mode: str) -> Optional[KernelImpl]:
    _ensure_builtins()
    return _REGISTRY.get(op, {}).get(mode)


def set_mode_override(mode: Optional[str], op: str = "*") -> None:
    """Install (or with ``mode=None`` clear) a per-op or global override.

    NOTE: overrides are consulted at *trace* time.  Flipping one mid-
    process does not invalidate already-compiled jit caches whose call
    sites resolved under the old override; prefer explicit ``mode=``
    arguments (fresh static-arg cache key) or the process-level env var.
    """
    if mode is not None and mode not in MODES + ("auto",):
        raise ValueError(f"mode must be one of {MODES + ('auto',)} or None")
    with _LOCK:
        if mode is None:
            _MODE_OVERRIDES.pop(op, None)
        else:
            _MODE_OVERRIDES[op] = mode


def resolve_mode(mode: Optional[str] = None, op: str = "*") -> str:
    """Collapse the precedence chain to a concrete mode (or "auto")."""
    if mode is not None and mode != "auto":
        if mode not in MODES:
            raise ValueError(f"unknown kernel mode {mode!r}; "
                             f"expected one of {MODES + ('auto',)}")
        return mode
    for key in (op, "*"):
        if key in _MODE_OVERRIDES:
            return _MODE_OVERRIDES[key]
    env = os.environ.get(ENV_VAR, "")
    if env:
        if env not in MODES + ("auto",):
            raise ValueError(f"{ENV_VAR}={env!r} is not one of "
                             f"{MODES + ('auto',)}")
        return env
    return "auto"


def coerce_mode(interpret: Optional[bool] = None,
                mode: Optional[str] = None) -> Optional[str]:
    """Back-compat shim: map a legacy ``interpret`` flag to a mode string.

    An explicit ``mode`` wins; an explicit boolean ``interpret`` maps to
    interpret/compiled; both-None defers to :func:`resolve_mode`.
    """
    if mode is not None:
        return mode
    if interpret is True:
        return "interpret"
    if interpret is False:
        return "compiled"
    return None


def legacy_mode(op: str, interpret: Optional[bool] = None,
                mode: Optional[str] = None) -> Optional[str]:
    """Mode for a call site that still carries a legacy ``interpret``
    flag.  An explicit ``mode=`` is user forcing and wins outright; the
    boolean is only a backend-derived *default*, so a process-level pin
    (per-op override / ``SNS_KERNEL_MODE``) beats it — that is what lets
    the CI kernel-matrix step pin a whole run to interpret/xla without
    touching every internal call site.  Both-None defers entirely."""
    if mode is not None:
        return mode
    pinned = resolve_mode(None, op)
    if pinned != "auto":
        return pinned
    return coerce_mode(interpret, None)


def resolve(op: str, *, mode: Optional[str] = None,
            backend: Optional[str] = None, shape: Tuple[int, ...] = (),
            dtype: Any = None) -> KernelImpl:
    """Pick the implementation for ``op``.  Fails loudly, never silently
    downgrades a forced mode."""
    _ensure_builtins()
    if op not in _REGISTRY:
        raise KeyError(f"unknown kernel op {op!r}; "
                       f"registered: {list_ops()}")
    if backend is None:
        backend = jax.default_backend()
    m = resolve_mode(mode, op)
    impls = _REGISTRY[op]
    if m != "auto":
        impl = impls.get(m)
        if impl is None:
            raise KernelUnavailableError(
                f"op {op!r} has no {m!r} implementation "
                f"(registered: {modes_of(op)})")
        if not impl.supported(backend, tuple(shape), dtype):
            raise KernelUnavailableError(
                f"op {op!r} mode {m!r} is not supported on backend "
                f"{backend!r} for shape {tuple(shape)} dtype {dtype}")
        return impl
    on_accel = backend in ACCELERATOR_BACKENDS
    for cand in MODES:  # compiled -> interpret -> xla
        impl = impls.get(cand)
        if impl is None or (cand == "interpret" and on_accel):
            continue
        if impl.prefer(backend, tuple(shape), dtype) \
                and impl.supported(backend, tuple(shape), dtype):
            return impl
    raise KernelUnavailableError(
        f"op {op!r}: no implementation accepts backend {backend!r} "
        f"(registered: {modes_of(op)})")


# ---------------------------------------------------------------------------
# Per-backend tile-size table + autotune cache
# ---------------------------------------------------------------------------

# VMEM/SMEM-conscious compiled-grid defaults.  "*" is the fallback row
# (CPU interpret mode is insensitive to these; the values keep the
# interpret grids identical to today's defaults so jit caches and tests
# are stable).  TPU rows keep the largest live block under ~2 MiB of
# VMEM at the adaptive grid cap G = 1024 (cic one-hots are (B, G) f32).
_TILE_TABLE: Dict[str, Dict[str, Dict[str, int]]] = {
    "cic_splat": {"tpu": {"block_items": 512},
                  "gpu": {"block_items": 1024},
                  "*": {"block_items": 1024}},
    "cic_gather": {"tpu": {"block_items": 512},
                   "gpu": {"block_items": 1024},
                   "*": {"block_items": 1024}},
    "knn_dist_tiles": {"*": {}},     # blocks are data-shape-determined
    "tsne_step": {"tpu": {"block": 128},
                  "gpu": {"block": 256},
                  "*": {"block": 256}},
    "segment_reduce": {"tpu": {"rows_per_block": 256, "edge_chunk": 512},
                       "gpu": {"rows_per_block": 128, "edge_chunk": 512},
                       "*": {"rows_per_block": 128, "edge_chunk": 256}},
}


def shape_bucket(shape: Tuple[int, ...]) -> str:
    """Next-pow2 bucket per dim: (1000, 2) -> "1024x2" (autotune keys)."""
    parts = []
    for s in shape:
        s = int(s)
        parts.append(str(s if s <= 1 else 1 << (s - 1).bit_length()))
    return "x".join(parts) if parts else "scalar"


def load_autotune_cache(path: str) -> Dict[str, Dict]:
    try:
        with open(path, "r", encoding="utf-8") as fh:
            data = json.load(fh)
    except (OSError, ValueError):
        return {}
    return data if isinstance(data, dict) else {}


def record_autotune(op: str, params: Dict[str, int], *, path: str,
                    backend: Optional[str] = None,
                    bucket: str = "") -> str:
    """Persist an autotune winner to the JSON file ``path``; returns the
    cache key written."""
    backend = backend or jax.default_backend()
    key = f"{backend}/{op}/{bucket or '*'}"
    cache = load_autotune_cache(path)
    cache[key] = dict(params)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(cache, fh, indent=2, sort_keys=True)
    os.replace(tmp, path)
    return key


def tile_params(op: str, *, backend: Optional[str] = None,
                shape: Tuple[int, ...] = None,
                cache_path: Optional[str] = None) -> Dict[str, int]:
    """Tile sizes for ``op`` from the committed table (backend row over
    the "*" row).  Only an explicit ``cache_path`` layers autotune
    winners on top: its ``backend/op/*`` entry, then the exact
    ``backend/op/bucket`` entry.  Nothing outside the checkout is read
    unless a caller names it."""
    backend = backend or jax.default_backend()
    table = _TILE_TABLE.get(op, {})
    base = dict(table.get("*", {}))
    base.update(table.get(backend, {}))
    if cache_path is None:
        return base
    cache = load_autotune_cache(cache_path)
    for key in (f"{backend}/{op}/*",
                f"{backend}/{op}/{shape_bucket(tuple(shape))}"
                if shape is not None else None):
        if key and key in cache and isinstance(cache[key], dict):
            base.update({k: int(v) for k, v in cache[key].items()})
    return base


def autotune_op(op: str, candidates, measure, *, cache_path: str,
                backend: Optional[str] = None,
                bucket: str = "") -> Dict[str, int]:
    """Empirical autotune: time ``measure(params)`` (seconds) for each
    candidate dict, persist the winner to ``cache_path``, return it.  Candidates that raise
    (e.g. a block size that exceeds VMEM) are skipped; all failing is an
    error."""
    backend = backend or jax.default_backend()
    best, best_t = None, float("inf")
    for params in candidates:
        try:
            t = float(measure(dict(params)))
        except Exception:                                    # noqa: BLE001
            continue
        if t < best_t:
            best, best_t = dict(params), t
    if best is None:
        raise KernelUnavailableError(
            f"autotune for op {op!r} on {backend!r}: every candidate failed")
    record_autotune(op, best, path=cache_path, backend=backend,
                    bucket=bucket)
    return best
