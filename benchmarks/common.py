"""Shared benchmark utilities: timing, CSV emission, JSON baselines with
a backend stamp, static jaxpr peak-buffer measurement (used by the
scaling benches to report memory trajectories past the point where
allocation would OOM)."""
from __future__ import annotations

import json
import os
import time
from typing import Callable, List, Optional

import jax
import numpy as np


def repo_root_json(name: str) -> str:
    """Absolute path of a tracked ``BENCH_*.json`` baseline at the repo
    root — the convention for benchmark trajectories kept under git."""
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), name)


def emit_json(payload: dict, json_out: Optional[str]) -> str:
    """Serialize a bench summary and optionally write it to ``json_out``.

    Stamps a ``backend`` column (``jax.default_backend()``) right after
    the bench name so every ``BENCH_*.json`` records where it ran — the
    tracked baselines are only comparable within a backend (ROADMAP
    item 4's CPU-vs-accelerator trajectory).  Returns the JSON string.
    """
    stamped = {"bench": payload.get("bench"),
               "backend": jax.default_backend()}
    stamped.update({k: v for k, v in payload.items() if k != "bench"})
    out = json.dumps(stamped, indent=2)
    if json_out:
        with open(json_out, "w") as f:
            f.write(out + "\n")
    return out


def iter_jaxpr_avals(jaxpr):
    """Yield every intermediate abstract value in a jaxpr, recursively."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if hasattr(v, "aval"):
                yield v.aval
        for p in eqn.params.values():
            for sub in _sub_jaxprs(p):
                yield from iter_jaxpr_avals(sub)


def _sub_jaxprs(param):
    vals = param if isinstance(param, (list, tuple)) else [param]
    for v in vals:
        if hasattr(v, "jaxpr"):          # ClosedJaxpr
            yield v.jaxpr
        elif hasattr(v, "eqns"):         # raw Jaxpr
            yield v


def count_primitive(jaxpr, name: str) -> int:
    """Occurrences of a primitive (e.g. "sort") in a jaxpr, recursively
    (scan/pjit bodies are traced once, so a scanned step's primitives are
    counted once regardless of trip count)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            n += 1
        for p in eqn.params.values():
            for sub in _sub_jaxprs(p):
                n += count_primitive(sub, name)
    return n


def count_eqns(jaxpr) -> int:
    """Total equation count of a jaxpr, recursively — a trace-size proxy."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for p in eqn.params.values():
            for sub in _sub_jaxprs(p):
                n += count_eqns(sub)
    return n


def peak_buffer_bytes(fn, *args) -> int:
    """Largest single intermediate of fn(*args), from the jaxpr (static)."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    best = 0
    for aval in iter_jaxpr_avals(jaxpr.jaxpr):
        if hasattr(aval, "shape") and hasattr(aval, "dtype"):
            best = max(best, int(np.prod(aval.shape, dtype=np.int64))
                       * aval.dtype.itemsize)
    return best


def interleaved_medians(drivers: dict, iters: int = 3) -> dict:
    """Time each zero-arg driver `iters` times in interleaved rounds (all
    are warmed first); median wall seconds per driver.  Interleaving keeps
    slow machine drift out of the variant RATIOS — shared by the
    throughput benches."""
    for once in drivers.values():
        once()                                 # warm the trace
    ts: dict = {k: [] for k in drivers}
    for _ in range(iters):
        for k, once in drivers.items():
            t0 = time.perf_counter()
            once()
            ts[k].append(time.perf_counter() - t0)
    return {k: sorted(v)[len(v) // 2] for k, v in ts.items()}


def time_fn(fn: Callable, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall seconds per call (block_until_ready)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


class Csv:
    def __init__(self, header: List[str]):
        self.header = header
        self.rows: List[List] = []

    def add(self, *row):
        self.rows.append(list(row))

    def dump(self, title: str) -> str:
        out = [f"# {title}", ",".join(self.header)]
        for r in self.rows:
            out.append(",".join(str(x) for x in r))
        return "\n".join(out)
