"""CPU rehearsal of every cell's set-up, window and check at a tiny size:
the result line has the contract's keys, ``checks`` comes last, and the
metrics are the cell's end-to-end ones (untraced) or the per-layer ones
that have something to read (traced)."""
import json

import pytest

from snsbench import harness
from snsbench.tests.tiny import run_tiny, workloads


@pytest.mark.parametrize("workload", workloads())
@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_cell_rehearsal(workload, trace):
    r = run_tiny(workload, trace=trace)
    json.dumps(r)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "checks"
    assert r["correct"] and r["attempted"] > 0 and r["failed"] == 0
    cell = harness.load_cell(workload)
    names = {m["name"] for m in (cell.per_layer if trace
                                 else cell.end_to_end)}
    assert set(r["metrics"]) <= names
    if not trace:
        assert set(r["metrics"]) == names
        assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["platform"] == "cpu"
