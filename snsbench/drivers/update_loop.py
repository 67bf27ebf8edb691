"""Traffic driver ``update_loop``: ``update()`` calls back to back.

A traffic file that names this driver (``"driver": "update_loop"``)
gives:

``pool_chunks``, ``chunk_points``
    the host-memory pool of chunks drawn from ``--seed`` (one mixture,
    disjoint draws: ``snsbench/data.py``) that the calls cycle through;
``fold_calls``
    ``update()`` calls made in set-up (the first compiles the fold).

The window folds one pool chunk per ``update()`` call, one call after
another; a call begun inside the window is finished and counted.  The
check compares the heavy hitters the service then serves with an exact
count of exactly the chunks fed (``reference.heavy_hitter_numbers``).

Each call's host-clock time is kept with what the calling thread did in
it (CPU and system seconds, involuntary context switches, page faults,
seconds in Python's garbage collector) and the CPU seconds of the whole
process (the runtime's threads too), and the slowest call's numbers
are reported beside the median call's: a call that stalls says whether
the thread worked, faulted, was preempted or waited.

``CONTROL`` and ``FAULTS`` are the variants the check has to catch.
"""
from __future__ import annotations

import gc
import resource
import time

import numpy as np

from snsbench import data, reference, sut

SPAN_NAMES = ("update",)

_THREAD = getattr(resource, "RUSAGE_THREAD", resource.RUSAGE_SELF)


def _usage():
    u = resource.getrusage(_THREAD)
    p = resource.getrusage(resource.RUSAGE_SELF)
    return np.array([u.ru_utime + u.ru_stime, u.ru_stime, u.ru_nivcsw,
                     u.ru_minflt, u.ru_majflt, p.ru_utime + p.ru_stime],
                    np.float64)


# the calling thread's, then the whole process's CPU seconds
USAGE = ("cpu_s", "sys_s", "invol_cs", "minflt", "majflt", "proc_cpu_s")


class GcClock:
    """Seconds spent in Python's garbage collector while installed."""

    def __init__(self):
        self.seconds, self.full = 0.0, 0
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.seconds += time.perf_counter() - self._t
            self.full += info.get("generation") == 2
            self._t = None

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


class Driver:
    def __init__(self, cell, seed: int, spans, variant=None, log=print):
        self.cell, self.seed, self.spans = cell, int(seed), spans
        self.variant, self.log = variant, log
        self.params = cell.traffic
        self.mix = data.Mixture.from_config(cell.config["data"])
        self.stream = data.Stream(self.mix, self.seed)
        self.calls = 0
        self.per_call = []         # (wall_s, *USAGE, gc_s) of window calls

    def feed(self):
        """One ``update()`` of the next pool chunk."""
        i = self.calls % len(self.pool)
        with self.spans("update"):
            self.svc.update(self.pool[i])
        self.fed[i] += 1
        self.calls += 1

    def setup(self):
        p = self.params
        t0 = time.perf_counter()
        self.pool = self.stream.pool(int(p["pool_chunks"]),
                                     int(p["chunk_points"]))
        self.fed = np.zeros(len(self.pool), np.int64)
        self.log(f"data: {len(self.pool)} chunks of {p['chunk_points']:,} "
                 f"{self.mix.dims}-D points, {time.perf_counter() - t0:.3f} s")
        self.cfg, self.svc = sut.build(self.cell.config, self.seed,
                                       self.mix.dims, self.variant)
        t0 = time.perf_counter()
        for _ in range(int(p["fold_calls"])):
            self.feed()
        self.log(f"fold: {self.calls} update() calls, "
                 f"{time.perf_counter() - t0:.3f} s")
        self.spans.events.clear()

    def window(self, t0: float, seconds: float) -> dict:
        first = self.calls
        with GcClock() as gclock:
            while True:
                u0, g0 = _usage(), gclock.seconds
                self.feed()
                u1 = _usage()
                self.per_call.append(
                    [self.spans.events[-1][2] - self.spans.events[-1][1],
                     *(u1 - u0), gclock.seconds - g0])
                if time.perf_counter() - t0 >= seconds:
                    break
        wall = time.perf_counter() - t0
        calls = self.calls - first
        per = np.array(self.per_call)
        slow = int(np.argmax(per[:, 0]))
        out = {"wall_s": wall, "attempted": calls, "failed": 0,
               "points": calls * int(self.params["chunk_points"]),
               "call_s_median": float(np.median(per[:, 0])),
               "call_cpu_s_median": float(np.median(per[:, 1])),
               "call_proc_cpu_s_median": float(np.median(per[:, 6])),
               "slow_call_at": slow, "slow_call_s": float(per[slow, 0]),
               "gc_full": gclock.full}
        for j, name in enumerate(USAGE + ("gc_s",)):
            out["slow_call_" + name] = float(per[slow, 1 + j])
        return out

    def check(self) -> dict:
        keys, est, bound = sut.served_heavy_hitters(self.svc, self.cfg)
        points = float(self.svc.health()["points"])
        fed = int(self.fed.sum()) * int(self.params["chunk_points"])
        self.svc = None
        uniq, counts = reference.exact_counts(self.pool, self.fed,
                                              self.cfg.bins)
        out = reference.heavy_hitter_numbers(
            keys, est, bound, uniq, counts, self.cfg.top_k,
            self.cfg.log2_cols)
        out["points_gap"] = abs(points - fed)
        return out


# ------------------------------------------------- control and faults
def sketch_bf16(svc, cfg):
    """The control: the sketch table in bfloat16 in place of float32 (the
    program's own ``dtype`` option of ``stream.init``)."""
    import jax
    import jax.numpy as jnp
    from repro.core import stream

    svc.state = stream.init(jax.random.key(cfg.seed), cfg.rows,
                            cfg.log2_cols, svc.state.cands.capacity,
                            dtype=jnp.bfloat16)


def update_unchanged(svc, cfg):
    """The fold returns the state unchanged."""
    def update(chunk):
        return {}
    svc.update = update


def update_half(svc, cfg):
    """Half of each chunk left out."""
    fold = svc.update

    def update(chunk):
        return fold(chunk[: len(chunk) // 2])
    svc.update = update


def update_altered(svc, cfg):
    """Every point counted one cell over in the first dimension."""
    fold = svc.update

    def update(chunk):
        moved = np.array(chunk)
        moved[:, 0] = np.minimum(moved[:, 0] + 1.0 / cfg.bins, 1.0)
        return fold(moved)
    svc.update = update


def reservoir_lowest(svc, cfg):
    """The reservoir merge keeps the lowest-count cells: after each fold
    the reservoir is replaced by the ``capacity`` cells of lowest count
    among the previous (faulty) reservoir and the chunk's exact runs,
    key-sorted as the program keeps it.  The sketch is folded as usual."""
    import jax.numpy as jnp
    from repro.core import candidates

    fold = svc.update
    cap = svc.state.cands.capacity
    pool = {"keys": np.zeros(0, np.uint64), "counts": np.zeros(0)}

    def update(chunk):
        out = fold(chunk)
        keys = np.concatenate([pool["keys"],
                               reference.cell_keys(np.asarray(chunk),
                                                   cfg.bins)])
        ones = np.ones(keys.size - pool["keys"].size)
        uniq, inv = np.unique(keys, return_inverse=True)
        counts = np.bincount(inv, np.concatenate([pool["counts"], ones]))
        keep = np.sort(np.lexsort((uniq, counts))[:cap])
        pool["keys"], pool["counts"] = uniq[keep], counts[keep]
        pad = cap - keep.size
        hi = np.concatenate([(pool["keys"] >> np.uint64(32)).astype(np.uint32),
                             np.full(pad, candidates.INVALID_KEY, np.uint32)])
        lo = np.concatenate([(pool["keys"] & np.uint64(0xFFFFFFFF)
                              ).astype(np.uint32),
                             np.full(pad, candidates.INVALID_KEY, np.uint32)])
        svc.state = svc.state._replace(cands=candidates.Candidates(
            key_hi=jnp.asarray(hi), key_lo=jnp.asarray(lo),
            count=jnp.asarray(np.concatenate([pool["counts"], np.zeros(pad)]),
                              jnp.float32),
            mask=jnp.asarray(np.arange(cap) < keep.size)))
        return out
    svc.update = update


CONTROL = sketch_bf16
FAULTS = [update_unchanged, update_half, update_altered, reservoir_lowest]
