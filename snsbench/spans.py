"""The benchmark's own host spans.

Each span is timed on the host clock and also written into the
profiler's trace as a ``TraceAnnotation`` of the same name, so that the
trace reduction can say what the host was doing in each device idle gap.
Spans are kept in memory; nothing is written while a window runs.
"""
from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self, annotation):
        self._annotation = annotation      # jax.profiler.TraceAnnotation
        self.events = []                   # (name, start_s, end_s)

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        with self._annotation(name):
            yield
        self.events.append((name, t0, time.perf_counter()))

    def durations(self, name: str) -> list:
        return [t1 - t0 for n, t0, t1 in self.events if n == name]
