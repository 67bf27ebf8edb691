#!/usr/bin/env python3
"""Per-stage breakdown of a cell of several sites (driver ``geo_loop``),
from the program's own spans and scopes.

    python3 snsbench/geo_stages.py --workload cancer.geo4 --seed 7 --seconds 10 --trace 1

As ``stages.py`` for an ingest cell: one set-up and window, no check,
one JSON object.  The scopes are read from the compiled text of both
programs a round runs, the sites' fold (``sns.fold.*``) and the master's
merge (``sns.geo.psum|gather|extract``).  With ``--trace 1``, ``sites``
gives:

* ``fold_device_s_per_site_mpoint``: the device seconds under the
  ``sns.fold.*`` scopes, summed over the chips, per 10^6 points folded
  (each chip's own site's points per its own seconds), comparable with
  an ingest cell's ``fold_device_s_per_mpoint.ingest``;
* ``<stage>_device_s_per_site_mpoint`` for each fold stage;
* ``merge_scopes_s_per_round``: the device seconds a chip spends in each
  ``sns.geo.*`` scope per round (mean over the chips);
* ``busy_s_per_chip``: each chip's busy seconds in the window.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(HERE))

from snsbench import stages, trace_reduce  # noqa: E402
from snsbench.trace_reduce import WINDOW_SPAN  # noqa: E402

SCOPE_PREFIXES = (stages.SCOPE_PREFIX, "sns.geo.")


def hlo_scopes(text: str) -> dict:
    """``{module: {instruction: scope}}`` of one compiled program: each
    instruction whose ``op_name`` has an ``sns.fold.*`` or ``sns.geo.*``
    component, with the outermost one."""
    module = stages.HLO_MODULE.search(text)
    scopes = {}
    for name, path in stages.HLO_OP.findall(text):
        inside = [c for c in path.split("/")
                  if c.startswith(SCOPE_PREFIXES)]
        if inside:
            scopes[name] = inside[0]
    return {module.group(1): scopes} if module and scopes else {}


def compiled_texts(op) -> list:
    """The compiled HLO text of the fold and of the merge that a
    ``geo_loop`` driver's service runs (cache hits after the window)."""
    import jax
    import numpy as np
    from repro.core import geo

    svc, cfg = op.svc, op.cfg
    shape = (op.sites, cfg.ingest_superbatch, cfg.ingest_chunk,
             op.mix.dims)
    on = svc.state.count.sharding
    return [
        geo.fold_sites.lower(
            svc.state, jax.ShapeDtypeStruct(shape, np.float32, sharding=on),
            jax.ShapeDtypeStruct(shape[:3], np.bool_, sharding=on),
            grid=svc.grid, mesh=svc.mesh).compile().as_text(),
        geo.merge_sites.lower(svc.state, mesh=svc.mesh, top_k=cfg.top_k
                              ).compile().as_text()]


def busy_per_chip(planes) -> list:
    """Each device plane's busy seconds inside the window."""
    spans = [e for p in planes
             if not p["name"].startswith(trace_reduce.DEVICE_PREFIX)
             for line in p["lines"] for e in line["events"]
             if e[0] == WINDOW_SPAN]
    _, w0, dur = max(spans, key=lambda e: e[2])
    out = []
    for p in planes:
        if p["name"].startswith(trace_reduce.DEVICE_PREFIX):
            ops = [(max(e[1], w0), min(e[1] + e[2], w0 + dur))
                   for line in p["lines"]
                   if line["name"] == trace_reduce.OPS_LINE
                   for e in line["events"]
                   if e[1] + e[2] > w0 and e[1] < w0 + dur]
            out.append(sum(e - s for s, e in trace_reduce.union(ops))
                       * 1e-9)
    return out


def per_site(r: dict, points: int, rounds: int) -> dict:
    """The fold's cost per 10^6 points of each chip's own site, and the
    merge scopes' seconds a round (see the module docstring)."""
    mpoints, n = points * 1e-6, r["chips"]
    fold = {s: r["scopes"].get(s, 0.0) * n / mpoints for s in stages.FOLD}
    out = {f"{s[len(stages.SCOPE_PREFIX):]}_device_s_per_site_mpoint": v
           for s, v in fold.items()}
    out["fold_device_s_per_site_mpoint"] = sum(fold.values())
    out["merge_scopes_s_per_round"] = {
        k: v / rounds for k, v in r["scopes"].items()
        if k.startswith("sns.geo.")}
    out["other_s_per_round"] = r["scopes"].get(stages.OTHER, 0.0) / rounds
    return out


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, cell=None, log=print) -> tuple:
    """One set-up and window of the cell; returns the printed object
    and the driver.  ``cell`` (tests) may replace what
    ``harness.load_cell`` finds."""
    from snsbench import harness
    from snsbench.spans import Spans

    cell = cell or harness.load_cell(workload)
    jax, _ = harness.start_jax(cell.chips, require_tpu)
    from repro import obs

    spans = Spans(jax.profiler.TraceAnnotation)
    mod = harness.driver(cell)
    op = mod.Driver(cell, seed, spans, variant=None, log=log)
    op.setup()
    calls = stages.CallSpans(op, obs.snapshot)
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
           "calls": calls.summary(),
           "program": obs.snapshot()}
    if trace:
        op_scopes = {}
        for text in compiled_texts(op):
            op_scopes.update(hlo_scopes(text))
        if not op_scopes:
            log("the compiled programs name no sns scope: a program "
                "without them, or a compile cache filled by one")
        planes = stages.load_xplane(trace_dir, mod.SPAN_NAMES, op_scopes)
        r = stages.reduce(planes, mod.SPAN_NAMES)
        if r is not None:
            out["trace"] = r
            out["busy_s_per_chip"] = busy_per_chip(planes)
            out["sites"] = per_site(r, window["points"],
                                    window["attempted"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    return out, op


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)

    def log(msg):
        print(f"[geo_stages] {msg}", file=sys.stderr, flush=True)

    from snsbench.harness import NoChip
    try:
        out, _ = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), log=log)
    except NoChip as e:
        log(f"refused: {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
