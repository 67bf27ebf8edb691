"""Host spans and counters of the program, process-wide and in memory.

``span(name)`` times a block on the host clock and marks it in the
profiler trace as a ``jax.profiler.TraceAnnotation`` of the same name, so
that a trace can say what the host was doing while the device idled.
``count(name, n)`` adds to a counter.  ``snapshot()`` returns the totals
as a plain dict::

    {"spans": {name: {"count": calls, "seconds": host seconds}},
     "counters": {name: total}}

Nothing is written or exported and there is no switch: a span costs one
annotation, two clock reads and a lock, a few microseconds.  Names are
constant strings under ``sns.``; device stages are named by
``jax.named_scope`` where the work is traced (``sns.fold.*``), not here.
"""
from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation

_LOCK = threading.Lock()
_SPANS: dict = {}        # name -> [calls, seconds]
_COUNTERS: dict = {}     # name -> total


class span:
    """Context manager: the block's host seconds and one call go to the
    totals of ``name``; the trace gets an annotation of that name."""
    __slots__ = ("name", "_annotation", "_t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        with _LOCK:
            total = _SPANS.setdefault(self.name, [0, 0.0])
            total[0] += 1
            total[1] += dt
        return False


def count(name: str, n=1) -> None:
    """Add ``n`` to the counter ``name``."""
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + n


def snapshot() -> dict:
    """The totals so far, as plain numbers."""
    with _LOCK:
        return {"spans": {k: {"count": c, "seconds": s}
                          for k, (c, s) in _SPANS.items()},
                "counters": dict(_COUNTERS)}
