"""The precision control of the ingest cells at a size a test run holds:
the sketch table in bfloat16 in place of float32 comes out not correct
under the cell's own limits, the program as it is comes out correct.

The cell's data is narrowed (cluster std 0.004) and the sketch cut to
2^14 columns, so that a few million points give heavy cells the counts
of thousands at which bfloat16 loses units, as the cell's window does at
its own size (readings in PERF.md)."""
import time

import pytest

from snsbench import harness
from snsbench.tests.tiny import tiny_cell


def _cell(workload):
    cell = tiny_cell(workload)
    cell.config["data"]["cluster_std"] = 0.004
    cell.config["sns"].update(ingest_chunk=8192, ingest_superbatch=4,
                              log2_cols=14)
    cell.traffic.update(chunk_points=65536, fold_calls=48)
    return cell


@pytest.mark.parametrize("workload", ["cancer.ingest", "sdss.ingest"])
@pytest.mark.parametrize("control", [False, True], ids=["sound", "control"])
def test_control_fails_the_sketch_limit(workload, control):
    cell = _cell(workload)
    variant = harness.driver(cell).CONTROL if control else None
    r = harness.run_cell(workload, 2147483001, 0.01, False,
                         t_start=time.perf_counter(), require_tpu=False,
                         variant=variant, cell=cell, log=lambda m: None)
    assert r["correct"] is not control, r["checks"]
    if control:
        assert r["checks"]["sketch_err_sigma"]["value"] > \
            r["checks"]["sketch_err_sigma"]["limit"]
