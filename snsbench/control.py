#!/usr/bin/env python3
"""Readings for the limits: a cell's correctness numbers from sound runs,
from its precision control and from its faults (the ``CONTROL`` and
``FAULTS`` of the cell's driver module), on the chip.

    python3 snsbench/control.py --workload cancer.ingest --seeds 11,12,13 \
        --seconds 10 --variants sound,control,reservoir_lowest

Each seed runs the cell once per variant named (``sound``: the program
as it is; ``control``; or a fault by its function's name), in one
process, and prints one JSON line per run: ``{"seed", "variant",
"correct", "numbers", "metrics"}``.  The benchmark's own runs never run
a variant.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from snsbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--variants", default="sound,control")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    mod = harness.driver(cell)
    known = {"sound": None, "control": mod.CONTROL}
    known.update({f.__name__: f for f in mod.FAULTS})
    runs = [(name, known[name]) for name in args.variants.split(",")]
    for seed in (int(s) for s in args.seeds.split(",")):
        for name, variant in runs:
            try:
                r = harness.run_cell(args.workload, seed, args.seconds, False,
                                     t_start=time.perf_counter(),
                                     variant=variant, cell=cell,
                                     log=lambda m: None)
            except harness.NoChip as e:
                print(f"control: refused: {e}", file=sys.stderr)
                return 2
            numbers = dict(r["readings"])
            numbers.update({k: c["value"] for k, c in r["checks"].items()})
            print(json.dumps({"seed": seed, "variant": name,
                              "correct": r["correct"], "numbers": numbers,
                              "metrics": r["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
