"""Tiny cells for CPU rehearsals of the harness (not for measuring)."""
from __future__ import annotations

import copy
import time

from snsbench import harness

# CPU rehearsals keep their compiled programs apart from the chip's
harness.CACHE_DIR = harness.os.path.join(harness.OUT, "test-cache")

SNS = {"top_k": 200, "log2_cols": 16, "ingest_chunk": 8192,
       "ingest_superbatch": 1}
TRAFFIC = {"pool_chunks": 2, "chunk_points": 8192, "fold_calls": 2}


def workloads() -> list:
    bench = harness.load_json(harness.os.path.join(harness.ROOT,
                                                   "BENCHMARK.json"))
    return [w["name"] for w in bench["workloads"]]


def tiny_cell(workload: str) -> harness.Cell:
    cell = copy.deepcopy(harness.load_cell(workload))
    cell.config["sns"].update(SNS)
    cell.traffic.update(TRAFFIC)
    return cell


def run_tiny(workload: str, seed: int = 3, seconds: float = 0.5,
             trace: bool = False, variant=None, cell=None) -> dict:
    return harness.run_cell(workload, seed, seconds, trace,
                            t_start=time.perf_counter(), require_tpu=False,
                            variant=variant, cell=cell or tiny_cell(workload),
                            log=lambda m: None)
