"""A run whose timed path is broken comes out not correct, once for each
fault of the cell's driver (``FAULTS``); a sound run of the same tiny
cell is correct.  Tiny cells on the CPU (the harness's look for a chip
is skipped).  The precision control needs a larger size: test_control.py."""
import pytest

from snsbench import harness
from snsbench.tests.tiny import run_tiny, tiny_cell, workloads


@pytest.mark.parametrize("workload", workloads())
def test_sound_run_is_correct(workload):
    r = run_tiny(workload)
    assert r["correct"], r["checks"]


@pytest.mark.parametrize("workload,fault", [
    (w, f.__name__) for w in workloads()
    for f in harness.driver(tiny_cell(w)).FAULTS])
def test_fault_is_caught(workload, fault):
    cell = tiny_cell(workload)
    variant = {f.__name__: f for f in harness.driver(cell).FAULTS}[fault]
    r = run_tiny(workload, variant=variant, cell=cell)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("workload", workloads())
def test_reservoir_fault_fails_on_which_cells_are_kept(workload):
    """The merge that keeps the lowest-count cells serves real cells with
    sound estimates: only ``top_half_missed_share`` can catch it."""
    cell = tiny_cell(workload)
    r = run_tiny(workload, cell=cell,
                 variant=harness.driver(cell).reservoir_lowest)
    c = r["checks"]
    assert c["top_half_missed_share"]["value"] > \
        c["top_half_missed_share"]["limit"]
    assert all(c[k]["value"] <= c[k]["limit"]
               for k in ("points_gap", "foreign_cells", "sketch_err_sigma"))
