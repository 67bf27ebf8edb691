#!/usr/bin/env python3
"""Per-stage breakdown of an ingest cell, from the program's own spans
and scopes.

    python3 snsbench/stages.py --workload cancer.ingest --seed 7 --seconds 10 --trace 1

Runs a cell's set-up and window as ``harness.run_cell`` does, without
its check, and prints one JSON object:

* ``window``: the driver's host-clock numbers;
* ``calls``: the seconds of the window's slowest ``update()`` call and
  of its median call, in all and in each program span (``repro.obs``):
  whether a stalled call waited in the final sync (``sns.update.wait``),
  the ``device_put``, the assembly of a superbatch or the dispatch.
  Untraced runs give these too;
* with ``--trace 1``, ``trace``: :func:`reduce` of the window, and
  ``stages``: :func:`per_stage`.

The program names its work (``src/repro/obs.py``): device stages as
``jax.named_scope``\\ s ``sns.fold.keys|sort|scatter|merge``, host spans
``sns.ingest.*`` and ``sns.update.wait``.  A v5e's ``XLA Ops`` events
carry no ``op_name``, so :func:`hlo_scopes` reads the scopes from the
compiled text of the fold the cell runs (a cache hit after the window),
by instruction name, and :func:`name_scopes` gives each op event the
scope of its instruction in the module run (line ``XLA Modules``) that
holds it.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import glob
import json
import os
import re
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(HERE))

from snsbench import trace_reduce  # noqa: E402
from snsbench.trace_reduce import DEVICE_PREFIX, OPS_LINE, WINDOW_SPAN  # noqa: E402

MODULES_LINE = "XLA Modules"
PROGRAM_PREFIX = "sns."
SCOPE_PREFIX = "sns.fold."
OTHER = "other"
FOLD = ("sns.fold.keys", "sns.fold.sort", "sns.fold.scatter",
        "sns.fold.merge")
FEED = ("sns.ingest.assemble", "sns.ingest.put")
CALL_SPANS = {"wait_s": "sns.update.wait", "put_s": "sns.ingest.put",
              "assemble_s": "sns.ingest.assemble",
              "dispatch_s": "sns.ingest.dispatch"}
HLO_MODULE = re.compile(r"^HloModule ([^ ,]+)", re.M)
HLO_OP = re.compile(r'^\s*(?:ROOT )?%([^ ]+) = .*?op_name="([^"]*)"', re.M)


def hlo_scopes(text: str) -> dict:
    """``{module: {instruction: scope}}`` from compiled HLO text: each
    instruction whose ``op_name`` has an ``sns.fold.*`` component, with
    the outermost one."""
    module = HLO_MODULE.search(text)
    scopes = {}
    for name, path in HLO_OP.findall(text):
        inside = [c for c in path.split("/") if c.startswith(SCOPE_PREFIX)]
        if inside:
            scopes[name] = inside[0]
    return {module.group(1): scopes} if module and scopes else {}


def name_scopes(planes: list, op_scopes: dict) -> list:
    """Give each device op event the scope of its instruction, as a
    fourth element, in the module whose ``XLA Modules`` event holds its
    start (a module event is named ``<module>(<fingerprint>)``)."""
    if not op_scopes:
        return planes
    for p in planes:
        if not p["name"].startswith(DEVICE_PREFIX):
            continue
        runs = sorted((e[1], e[1] + e[2], e[0].partition("(")[0])
                      for line in p["lines"] if line["name"] == MODULES_LINE
                      for e in line["events"])
        starts = [r[0] for r in runs]
        for line in p["lines"]:
            if line["name"] != OPS_LINE:
                continue
            for e in line["events"]:
                i = bisect.bisect_right(starts, e[1]) - 1
                if i < 0 or e[1] >= runs[i][1]:
                    continue
                scope = op_scopes.get(runs[i][2], {}).get(
                    e[0].partition(" = ")[0].lstrip("%"))
                if scope is not None:
                    e[3:] = [scope]
    return planes


def load_xplane(trace_dir: str, span_names, op_scopes=None) -> list:
    """``trace_reduce.load_xplane``'s planes, with the ``XLA Modules``
    line of each device plane and the program's host spans (``sns.*``)
    kept too, and each op event's scope named by ``op_scopes``."""
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
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [[e.name, e.start_ns, e.duration_ns] for e in line.events
                      if device or e.name in keep
                      or e.name.startswith(PROGRAM_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return name_scopes(planes, op_scopes or {})


def innermost(spans, points) -> list:
    """For each of the ascending ``points``, the name of the innermost
    of the ``spans`` (name, start, end) that holds it, or None.  Spans
    nest or follow one another."""
    order = sorted(spans, key=lambda h: (h[1], -h[2]))
    out, stack, i = [], [], 0
    for t in points:
        while i < len(order) and order[i][1] <= t:
            stack.append(order[i])
            i += 1
        while stack and stack[-1][2] <= t:
            stack.pop()
        out.append(stack[-1][0] if stack else None)
    return out


def reduce(planes, span_names) -> dict | None:
    """``trace_reduce.reduce`` of the planes, its keys as it gives them,
    and the program's view of the same window:

    * ``scopes``: device self-seconds per stage scope (mean over the
      chips), ``other`` for ops with none;
    * ``scoped_ops``: the top ops by self time, named with their scope;
    * ``host_spans``: host self-seconds of each program span;
    * ``idle_spans``: the first chip's idle seconds, each gap named by
      the innermost span, benchmark or program, that holds its midpoint.
    """
    out = trace_reduce.reduce(planes, span_names)
    if out is None:
        return None
    spans = [(e[0], e[1], e[1] + e[2]) for p in planes
             if not p["name"].startswith(DEVICE_PREFIX)
             for line in p["lines"] for e in line["events"]
             if e[0] in span_names or e[0] == WINDOW_SPAN
             or e[0].startswith(PROGRAM_PREFIX)]
    _, w0, w1 = max((s for s in spans if s[0] == WINDOW_SPAN),
                    key=lambda s: s[2] - s[1])
    op_ns, scope_ns, first = (collections.Counter(), collections.Counter(),
                              None)
    for p in planes:
        if not p["name"].startswith(DEVICE_PREFIX):
            continue
        inside = [(max(e[1], w0), min(e[1] + e[2], w1),
                   (trace_reduce.op_name(e[0]), e[3] if len(e) > 3 else None))
                  for line in p["lines"] if line["name"] == OPS_LINE
                  for e in line["events"] if e[1] + e[2] > w0 and e[1] < w1]
        for (_, _, (name, scope)), t in zip(inside,
                                            trace_reduce.self_times(inside)):
            op_ns[f"{name} {scope}" if scope else name] += t
            scope_ns[scope or OTHER] += t
        if first is None:
            first = trace_reduce.union([(s, e) for s, e, _ in inside])
    gaps, mids, cursor = [], [], w0
    for s, e in first + [[w1, w1]]:
        if s > cursor:
            gaps.append(s - cursor)
            mids.append(0.5 * (cursor + s))
        cursor = max(cursor, e)
    idle = collections.Counter()
    inner = [h for h in spans if h[0] != WINDOW_SPAN]
    for name, gap in zip(innermost(inner, mids), gaps):
        idle[name or WINDOW_SPAN] += gap
    program = [(max(s, w0), min(e, w1), name) for name, s, e in spans
               if name.startswith(PROGRAM_PREFIX) and e > w0 and s < w1]
    host_ns = collections.Counter()
    for (_, _, name), t in zip(program, trace_reduce.self_times(program)):
        host_ns[name] += t
    n = out["chips"]
    out["scopes"] = {k: v * 1e-9 / n for k, v in scope_ns.most_common()}
    out["scoped_ops"] = [[k, v * 1e-9 / n]
                         for k, v in op_ns.most_common(trace_reduce.TOP)]
    out["host_spans"] = {k: v * 1e-9 for k, v in host_ns.most_common()}
    out["idle_spans"] = {k: v * 1e-9 for k, v in idle.most_common()}
    return out


def per_stage(r: dict, points: int) -> dict:
    """The stage numbers of a reduced window of ``points`` points: device
    and feed seconds per 10^6 points, the share of the window idle while
    the innermost span was a feed span, and the scoped share of busy."""
    mpoints = points * 1e-6
    out = {f"{s[len(SCOPE_PREFIX):]}_device_s_per_mpoint":
           r["scopes"].get(s, 0.0) / mpoints for s in FOLD}
    out["feed_host_s_per_mpoint"] = sum(
        r["host_spans"].get(k, 0.0) for k in FEED) / mpoints
    out["feed_idle_pct"] = 100.0 * sum(
        r["idle_spans"].get(k, 0.0) for k in FEED) / r["window_s"]
    out["scoped_pct_of_busy"] = 100.0 * (
        1.0 - r["scopes"].get(OTHER, 0.0) / r["busy_s"])
    return out


class CallSpans:
    """Wraps a driver's ``feed()``: per call, its wall seconds and its
    seconds in each of ``CALL_SPANS`` (deltas of ``snapshot()``)."""

    def __init__(self, op, snapshot):
        self._feed, self._snapshot = op.feed, snapshot
        self.calls = []
        op.feed = self.feed

    def _seconds(self):
        spans = self._snapshot()["spans"]
        return [spans.get(n, {"seconds": 0.0})["seconds"]
                for n in CALL_SPANS.values()]

    def feed(self):
        s0, t0 = self._seconds(), time.perf_counter()
        self._feed()
        t1, s1 = time.perf_counter(), self._seconds()
        self.calls.append([t1 - t0] + [b - a for a, b in zip(s0, s1)])

    def summary(self) -> dict:
        """The slowest call's seconds beside the median call's."""
        import numpy as np

        per = np.array(self.calls)
        slow = int(np.argmax(per[:, 0]))
        out = {"calls": len(per), "slow_call_at": slow,
               "slow_call_s": float(per[slow, 0]),
               "call_s_median": float(np.median(per[:, 0]))}
        for j, name in enumerate(CALL_SPANS, 1):
            out["slow_call_" + name] = float(per[slow, j])
            out[f"call_{name}_median"] = float(np.median(per[:, j]))
        return out


def compiled_text(op) -> str:
    """The compiled HLO text of the fold that an ``update_loop`` driver's
    service runs."""
    import jax
    import numpy as np
    from repro.core import stream

    b, n = op.cfg.ingest_superbatch, op.cfg.ingest_chunk
    fold, lead = ((stream.ingest_superbatch, (b, n)) if b > 1
                  else (stream.ingest_chunk, (n,)))
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), op.svc.state)
    return fold.lower(
        state, jax.ShapeDtypeStruct(lead + (op.mix.dims,), np.float32),
        jax.ShapeDtypeStruct(lead, np.bool_),
        grid=op.svc.grid).compile().as_text()


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, cell=None, log=print) -> dict:
    """One set-up and window of the cell; returns the printed object
    and the driver.
    ``cell`` (tests) may replace what ``harness.load_cell`` finds."""
    from snsbench import harness
    from snsbench.spans import Spans

    cell = cell or harness.load_cell(workload)
    jax, _ = harness.start_jax(cell.chips, require_tpu)
    from repro import obs

    spans = Spans(jax.profiler.TraceAnnotation)
    mod = harness.driver(cell)
    op = mod.Driver(cell, seed, spans, variant=None, log=log)
    op.setup()
    calls = CallSpans(op, obs.snapshot)
    trace_dir = os.path.join(harness.OUT, "stages", workload)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    with spans(WINDOW_SPAN):
        window = op.window(t0, seconds)
    if trace:
        jax.profiler.stop_trace()
    out = {"workload": workload, "seed": seed,
           "window": {k: v for k, v in window.items()
                      if isinstance(v, (int, float))},
           "calls": calls.summary()}
    if trace:
        op_scopes = hlo_scopes(compiled_text(op))
        if not op_scopes:
            # JAX's compile cache keys leave op metadata out, so an entry
            # compiled from a program without the scopes hides them too
            log("the compiled fold names no sns.fold scope: a program "
                "without them, or a compile cache filled by one")
        planes = load_xplane(trace_dir, mod.SPAN_NAMES, op_scopes)
        r = reduce(planes, mod.SPAN_NAMES)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if r is not None:
            out["trace"] = r
            out["stages"] = per_stage(r, window["points"])
    return out, op


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)

    def log(msg):
        print(f"[stages] {msg}", file=sys.stderr, flush=True)

    from snsbench.harness import NoChip
    try:
        out, _ = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  log=log)
    except NoChip as e:
        log(f"refused: {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
