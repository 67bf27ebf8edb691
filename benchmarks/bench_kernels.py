"""Per-op kernel-tier microbenchmarks over the dispatch registry.

Every op registered in ``repro.kernels.registry`` (cic splat/gather, the
kNN distance scan, the fused tSNE force tile, the fused segment reduce)
is timed under every mode it supports on this backend — compiled vs
interpret vs the pure-XLA reference — median-of-3 via
``common.time_fn``.  Modes a backend cannot run (compiled on CPU) are
reported as skipped, never silently dropped: the row is the evidence
that the tier was considered.

On CPU the interpret timings are NOT accelerator predictions — the value
is (a) correctness at benchmark scale (``--smoke`` turns the
auto-vs-XLA comparison into a hard CI gate) and (b) the tracked
per-mode trajectory in ``BENCH_kernels.json`` (backend-stamped by
``common.emit_json``, so baselines are only compared within a backend).

``--autotune OUT.json`` sweeps tile-size candidates for each tunable op
and writes the winners to OUT.json (keyed by ``backend/op/shape-bucket``).
The program never reads that file by itself: its tile sizes come from
the committed ``registry._TILE_TABLE``, so a winner takes effect once
someone copies it into that table.
"""
from __future__ import annotations

import argparse
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Csv, emit_json, repo_root_json, time_fn
from repro.core import coo
from repro.kernels import knn_tile, ops, registry

DEFAULT_JSON = repo_root_json("BENCH_kernels.json")

# auto-vs-XLA gate: interpret and compiled reassociate fp sums, so the
# tolerance is fp32-accumulation-loose, not bitwise (the bitwise claims
# live in tests/test_kernel_registry.py on exact-integer payloads)
_RTOL, _ATOL = 1e-4, 1e-5


def _inputs(n: int) -> Dict[str, jnp.ndarray]:
    """Deterministic shared inputs for every op at problem size n."""
    kk = jax.random.split(jax.random.key(0), 9)
    g = 64
    pts = jax.random.uniform(kk[0], (n, 2), jnp.float32, 0.0, g - 1.001)
    m = min(n, 2048)                     # tsne tile is O(m²): keep modest
    t, b, d = max(1, n // 4096), 128, 8  # knn tiles: B queries, 3B window
    rows, fan = max(8, n // 8), 8        # sorted-COO, uniform fan-out
    return {
        "grid_size": g,
        "i0": jnp.floor(pts).astype(jnp.int32),
        "frac": pts - jnp.floor(pts),
        "masses": jax.random.normal(kk[1], (n, 2), jnp.float32),
        "fields": jax.random.normal(kk[2], (2, g, g), jnp.float32),
        "x": jax.random.normal(kk[3], (m, 8), jnp.float32),
        "y": jax.random.normal(kk[4], (m, 2), jnp.float32),
        "beta": jnp.ones((m,), jnp.float32),
        "zp": jnp.full((m,), float(m), jnp.float32),
        "qx": jax.random.normal(kk[5], (t, b, d), jnp.float32),
        "qid": jnp.arange(t * b, dtype=jnp.int32).reshape(t, b),
        "cx": jax.random.normal(kk[6], (t, 3 * b, d), jnp.float32),
        "cid": jax.random.randint(kk[7], (t, 3 * b), -1, t * b,
                                  dtype=jnp.int32),
        "vals": jax.random.normal(kk[8], (rows * fan, 2), jnp.float32),
        "bounds": jnp.arange(rows + 1, dtype=jnp.int32) * fan,
    }


def _cases(v: Dict[str, jnp.ndarray]) -> List[Tuple[str, object]]:
    """One entry per registered op: ``(op, make)`` where ``make(mode)``
    is a zero-arg driver returning the op's output array."""
    return [
        ("cic_splat", lambda mode: (
            lambda: ops.cic_splat(v["i0"], v["frac"], v["masses"],
                                  v["grid_size"], mode=mode))),
        ("cic_gather", lambda mode: (
            lambda: ops.cic_gather(v["fields"], v["i0"], v["frac"],
                                   mode=mode))),
        ("knn_dist_tiles", lambda mode: (
            lambda: knn_tile.distance_tiles(v["qx"], v["qid"], v["cx"],
                                            v["cid"], mode=mode))),
        ("tsne_step", lambda mode: (
            lambda: ops.tsne_step_fused(v["x"], v["y"], v["beta"],
                                        v["zp"], mode=mode))),
        ("segment_reduce", lambda mode: (
            lambda: coo.segment_reduce(v["vals"], v["bounds"],
                                       mode=mode))),
    ]


def _maxdiff(a: np.ndarray, b: np.ndarray) -> float:
    """Max |a−b| over finite entries (+inf == +inf counts as equal)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    both_inf = np.isinf(a) & np.isinf(b) & (np.sign(a) == np.sign(b))
    # zero matched infinities BEFORE subtracting (inf - inf is nan)
    d = np.abs(np.where(both_inf, 0.0, a) - np.where(both_inf, 0.0, b))
    return float(np.max(d)) if d.size else 0.0


def run(n: int = 1 << 16, *, smoke: bool = False,
        json_out: Optional[str] = None,
        autotune: Optional[str] = None) -> str:
    """Bench (or, with ``smoke=True``, gate) every registered op.

    ``smoke`` shrinks the problem, keeps median-of-3 timing, and turns
    the auto-resolution-vs-XLA comparison into an ``AssertionError`` —
    the CI contract that whatever impl auto picks on this backend agrees
    numerically with the ground-truth reference.
    """
    if smoke:
        n = min(n, 4096)
    backend = jax.default_backend()
    v = _inputs(n)
    csv = Csv(["op", "mode", "backend", "seconds", "max_abs_diff_vs_xla",
               "notes"])
    ops_json: Dict[str, dict] = {}
    failures: List[str] = []

    for op, make in _cases(v):
        results: Dict[str, np.ndarray] = {}
        entry: Dict[str, dict] = {}
        auto_mode = registry.resolve(op).mode
        for mode in registry.modes_of(op):
            driver = make(mode)
            try:
                out = np.asarray(jax.block_until_ready(driver()))
            except registry.KernelUnavailableError as e:
                csv.add(op, mode, backend, "skipped", "",
                        f"unsupported: {e}")
                entry[mode] = {"skipped": str(e)}
                continue
            results[mode] = out
            secs = time_fn(driver)
            entry[mode] = {"seconds": round(secs, 6)}
            note = "auto pick" if mode == auto_mode else ""
            csv.add(op, mode, backend, f"{secs:.5f}", "", note)
        # per-mode deviation from the XLA reference
        ref = results.get("xla")
        for mode, out in results.items():
            if ref is None:
                break
            diff = _maxdiff(out, ref)
            entry[mode]["max_abs_diff_vs_xla"] = diff
            for row in csv.rows:
                if row[0] == op and row[1] == mode:
                    row[4] = f"{diff:.2e}"
        ops_json[op] = {"auto_mode": auto_mode, "modes": entry}
        if smoke:
            if ref is None or auto_mode not in results:
                failures.append(f"{op}: auto mode {auto_mode!r} or xla "
                                f"reference did not produce a result")
            elif not np.allclose(results[auto_mode], ref,
                                 rtol=_RTOL, atol=_ATOL):
                failures.append(
                    f"{op}: auto-resolved mode {auto_mode!r} deviates "
                    f"from xla reference by "
                    f"{_maxdiff(results[auto_mode], ref):.3e} "
                    f"(rtol={_RTOL}, atol={_ATOL})")

    if autotune:
        for row in _run_autotune(n, v, autotune):
            csv.add(*row)

    payload = {"bench": "kernels", "n": n, "smoke": smoke, "ops": ops_json}
    emit_json(payload, json_out)
    if failures:
        raise AssertionError(
            "bench_kernels --smoke gate failed:\n  " + "\n  ".join(failures))
    title = f"kernel_tiers (per-op compiled/interpret/xla, backend={backend}"
    title += ", SMOKE GATE PASSED)" if smoke else ")"
    return csv.dump(title)


def _run_autotune(n: int, v: Dict[str, jnp.ndarray], out_path: str):
    """Sweep tile candidates through the PUBLIC wrappers (so padding
    logic sees each candidate) and write winners to ``out_path``.
    Yields CSV rows describing each winner."""
    backend = jax.default_backend()
    # the best pallas tier this backend actually runs; nothing to tune
    # when auto already lands on the pure-XLA path everywhere
    mode = "compiled" if backend in registry.ACCELERATOR_BACKENDS \
        else "interpret"
    seg_impl = registry.get("segment_reduce", mode)
    sweeps = {
        "cic_splat": (
            [{"block_items": s} for s in (256, 512, 1024, 2048)],
            lambda p: time_fn(lambda: ops.cic_splat(
                v["i0"], v["frac"], v["masses"], v["grid_size"],
                mode=mode, **p))),
        "cic_gather": (
            [{"block_items": s} for s in (256, 512, 1024, 2048)],
            lambda p: time_fn(lambda: ops.cic_gather(
                v["fields"], v["i0"], v["frac"], mode=mode, **p))),
        "tsne_step": (
            [{"block": s} for s in (128, 256, 512)],
            lambda p: time_fn(lambda: ops.tsne_step_fused(
                v["x"], v["y"], v["beta"], v["zp"], mode=mode, **p))),
        "segment_reduce": (
            [{"rows_per_block": r, "edge_chunk": c}
             for r in (64, 128, 256) for c in (256, 512)],
            lambda p: time_fn(lambda: seg_impl.fn(
                v["vals"], v["bounds"], **p))),
    }
    for op, (candidates, measure) in sweeps.items():
        try:
            best = registry.autotune_op(
                op, candidates, measure, cache_path=out_path,
                bucket=registry.shape_bucket((n,)))
        except registry.KernelUnavailableError as e:
            yield (op, mode, backend, "skipped", "", f"autotune: {e}")
            continue
        yield (op, mode, backend, "", "", f"autotuned winner: {best}")


def main() -> None:
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=1 << 16)
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: assert auto resolution matches the "
                         "XLA reference per op (small n)")
    ap.add_argument("--autotune", default=None, metavar="OUT_JSON",
                    help="sweep tile-size candidates and write the "
                         "winners to OUT_JSON")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write BENCH_kernels-style JSON here "
                         "(default: no file)")
    args = ap.parse_args()
    print(run(args.n, smoke=args.smoke, json_out=args.json,
              autotune=args.autotune))


if __name__ == "__main__":
    main()
