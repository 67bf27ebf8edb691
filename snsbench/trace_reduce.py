"""Reduce a profiler trace to the benchmark's device numbers.

The input is a list of planes, each ``{"name", "lines": [{"name",
"events": [[name, start_ns, duration_ns], ...]}]}``: the shape of the
``.xplane.pb`` that ``jax.profiler`` writes, as :func:`load_xplane` reads
it, and of the small fixture the tests check the reduction on.

* Device planes are those whose name starts with ``/device:TPU``.  Their
  operations are the events of the line ``XLA Ops``, named by their HLO
  instruction and its first result (``fusion.12 f32[8]`` of
  ``%fusion.12 = f32[8]{0} fusion(...)``).
  The asynchronous copies of the line ``Async XLA Ops`` are not counted.
* The window is the benchmark's ``bench_loop`` span on a host plane.
* Busy time is, per chip, the length of the union of the operation
  intervals inside the window; ``busy_s`` is its mean over the chips.
  The idle share is ``1 - busy_s / window_s``.
* An operation's time in ``device_ops`` is its self time: a ``while``
  or ``conditional`` event holds the events of its body, which are
  taken out of it.
* Each idle gap of the first chip is named by the benchmark span (from
  ``span_names``; they follow one another and do not nest) that holds
  its midpoint, or ``bench_loop``.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

SHAPE = re.compile(r"[a-z][a-z0-9]*\[[0-9,]*\]")
DEVICE_PREFIX = "/device:TPU"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench_loop"
TOP = 10


def load_xplane(trace_dir: str, span_names) -> list:
    """Planes of the newest ``.xplane.pb`` under ``trace_dir``: the
    operation line of each device plane, and the host events named in
    ``span_names`` (the rest of the host lines is dropped)."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return []
    keep = set(span_names) | {WINDOW_SPAN}
    planes = []
    for plane in ProfileData.from_file(files[-1]).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            events = [[e.name, e.start_ns, e.duration_ns] for e in line.events
                      if device or e.name in keep]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def op_name(event_name: str) -> str:
    """``fusion.12 f32[8]`` for ``%fusion.12 = f32[8]{0} fusion(...)``."""
    name, _, rest = event_name.partition(" = ")
    shape = SHAPE.match(rest.lstrip("("))
    return f"{name.lstrip('%')} {shape.group(0)}" if shape \
        else name.lstrip("%")


def self_times(intervals):
    """Per interval (start, end, name), its length minus the intervals
    directly nested in it."""
    order = sorted(range(len(intervals)),
                   key=lambda i: (intervals[i][0], -intervals[i][1]))
    own = [e - s for s, e, _ in intervals]
    stack = []
    for i in order:
        s, e, _ = intervals[i]
        while stack and intervals[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, intervals[stack[-1]][1]) - s
        stack.append(i)
    return own


def union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint union."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(planes, span_names) -> dict | None:
    """Busy, idle and breakdown numbers of the traced window; None when
    the trace has no window span or no device operation in it."""
    spans = [(e[0], e[1], e[1] + e[2]) for p in planes
             if not p["name"].startswith(DEVICE_PREFIX)
             for line in p["lines"] for e in line["events"]
             if e[0] in span_names or e[0] == WINDOW_SPAN]
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows:
        return None
    _, w0, w1 = max(windows, key=lambda s: s[2] - s[1])
    chips = []
    op_ns = collections.Counter()
    for p in planes:
        if not p["name"].startswith(DEVICE_PREFIX):
            continue
        ops = [e for line in p["lines"] if line["name"] == OPS_LINE
               for e in line["events"]]
        inside = [(max(e[1], w0), min(e[1] + e[2], w1), op_name(e[0]))
                  for e in ops if e[1] + e[2] > w0 and e[1] < w1]
        for (_, _, name), t in zip(inside, self_times(inside)):
            op_ns[name] += t
        chips.append(union([(s, e) for s, e, _ in inside]))
    if not chips or not any(chips):
        return None
    window_ns = w1 - w0
    busy_ns = sum(sum(e - s for s, e in c) for c in chips) / len(chips)
    gaps = collections.Counter()
    inner = sorted((s for s in spans if s[0] != WINDOW_SPAN),
                   key=lambda h: h[1])
    starts = [h[1] for h in inner]
    cursor = w0
    for s, e in chips[0] + [[w1, w1]]:
        if s > cursor:
            mid = 0.5 * (cursor + s)
            i = bisect.bisect_right(starts, mid) - 1
            name = inner[i][0] if i >= 0 and mid < inner[i][2] \
                else WINDOW_SPAN
            gaps[name] += s - cursor
        cursor = max(cursor, e)
    n = len(chips)
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "idle_pct": 100.0 * (1.0 - busy_ns / window_ns),
        "chips": n,
        "device_ops": [[k, v * 1e-9 / n] for k, v in op_ns.most_common(TOP)],
        "idle_gaps": [[k, v * 1e-9] for k, v in gaps.most_common(TOP)],
    }
