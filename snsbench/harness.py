"""One run of one benchmark cell: set-up, measured window, check.

    python3 snsbench/run.py --workload cancer.ingest --seed 7 --seconds 10 --trace 0

Everything a cell is made of is found by name, from ``BENCHMARK.json``:
its configuration in ``snsbench/configs/<config>.json``, its traffic mix
in ``snsbench/traffic/<traffic>.json`` (the parameters of the mix, and the
name of the driver that reads them: ``snsbench/drivers/<driver>.py``), the
limits of its correctness numbers in ``snsbench/limits/<workload>.json``,
and one reader per metric in ``snsbench/metrics/<metric>.py``.

A driver module has ``SPAN_NAMES`` (its host spans, by which the trace
reduction names idle gaps), ``CONTROL`` and ``FAULTS`` (the variants its
check has to catch) and a class ``Driver(cell, seed, spans, variant,
log)`` with ``setup()``, ``window(t0, seconds)`` (the host-clock numbers
of the window, with ``attempted`` and ``failed``) and ``check()`` (the
numbers that the cell's limits compare).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
``breakdown`` (traced runs) and, last, ``checks``: each number compared
with its limit.  The same numbers are the last lines of standard error.
The run exits 2, with no result line, when the repository's ``src/`` is
missing or JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SRC = os.path.join(ROOT, "src")
OUT = os.path.join(HERE, "out")
CACHE_DIR = os.path.join(OUT, "jax_cache")


class NoChip(RuntimeError):
    """The run cannot measure: no TPU, too few chips, or no program."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list      # metric entries of BENCHMARK.json
    per_layer: list


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str) -> Cell:
    """The cell of ``BENCHMARK.json`` named ``workload``."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {w["name"]: w for w in bench["workloads"]}.get(workload)
    if entry is None:
        raise SystemExit(f"unknown workload {workload!r}")
    config = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config=load_json(os.path.join(ROOT, config["file"])),
        traffic=load_json(os.path.join(HERE, "traffic",
                                       entry["traffic"] + ".json")),
        limits=load_json(os.path.join(HERE, "limits", workload + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)])


def _module(kind: str, name: str):
    """The module ``snsbench/<kind>/<name>.py``."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"snsbench_{kind}_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    return _module("metrics", name).read


def driver(cell: Cell):
    """The driver module that the cell's traffic mix names."""
    return _module("drivers", cell.traffic["driver"])


@dataclasses.dataclass
class Context:
    """What the metric readers read."""
    setup_s: float
    window: dict                 # the driver's host-clock numbers
    spans: object                # spans.Spans of the window
    compiles_in_window: int
    trace: dict | None = None    # trace_reduce.reduce(...)


def _device(jax, devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "device_kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def start_jax(chips: int, require_tpu: bool):
    """Import JAX with the benchmark's compile cache; refuse without a
    TPU (unless a test asks to run on whatever JAX finds)."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise NoChip(f"no program source at {SRC}")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro import compile_cache
    compile_cache.enable()
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {devices[0].platform} devices")
    if require_tpu and len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    return jax, devices[:chips]


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True, variant=None,
             cell: Cell | None = None, log=print) -> dict:
    """One run; returns the result object.  ``variant(svc, cfg)`` (tests
    and the control script) may break or replace the service after it is
    built; ``cell`` may replace what ``load_cell`` finds."""
    cell = cell or load_cell(workload)
    jax, devices = start_jax(cell.chips, require_tpu)
    log(f"jax: {len(devices)} x {devices[0].device_kind}, "
        f"{time.perf_counter() - t_start:.3f} s after start")
    from snsbench.compile_meter import CompileMeter
    from snsbench.spans import Spans
    from snsbench import trace_reduce

    meter = CompileMeter(jax)
    spans = Spans(jax.profiler.TraceAnnotation)
    mod = driver(cell)
    op = mod.Driver(cell, seed, spans, variant=variant, log=log)
    op.setup()
    trace_dir = os.path.join(OUT, "trace", workload)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    c0 = meter.snapshot()
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    log(f"setup: {setup_s:.3f} s, of it compiling {c0[0]:.3f} s "
        f"({c0[1]} programs)")
    with spans(trace_reduce.WINDOW_SPAN):
        window = op.window(t_window, seconds)
    log("window: " + json.dumps({k: v for k, v in window.items()
                                 if isinstance(v, (int, float))}))
    c1 = meter.snapshot()
    if trace:
        jax.profiler.stop_trace()
    device = _device(jax, devices)
    ctx = Context(setup_s=setup_s, window=window, spans=spans,
                  compiles_in_window=c1[1] - c0[1])
    breakdown = None
    if trace:
        planes = trace_reduce.load_xplane(trace_dir, mod.SPAN_NAMES)
        ctx.trace = trace_reduce.reduce(planes, mod.SPAN_NAMES)
        if ctx.trace is not None:
            device["busy_s"] = ctx.trace["busy_s"]
            device["window_s"] = ctx.trace["window_s"]
            breakdown = {"device_ops": ctx.trace["device_ops"],
                         "idle_gaps": ctx.trace["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    numbers = op.check()
    limits = cell.limits["limits"]
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": window["failed"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window"] = {k: v for k, v in window.items()
                        if isinstance(v, (int, float))}
    result["compiles_in_window"] = ctx.compiles_in_window
    result["readings"] = {k: v for k, v in numbers.items() if k not in limits}
    result["checks"] = checks
    return result


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg):
        print(f"[snsbench] {msg}", file=sys.stderr, flush=True)

    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start, log=log)
    except NoChip as e:
        log(f"refused: {e}")
        return 2
    for name, v in result["readings"].items():
        print(f"reading {name} = {v!r} (not compared)", file=sys.stderr,
              flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
