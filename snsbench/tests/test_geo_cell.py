"""The four-site cell at sizes a test holds, on the CPU (four sites on
one device): its precision control fails the sketch limit, and each
fault of its driver is caught by the number that should catch it.

The control's data is narrowed and its sketch cut as in test_control.py,
so that a few million points give heavy cells the counts at which
bfloat16 loses units, as the cell's window does at its own size."""
import time

import pytest

from snsbench import harness
from snsbench.tests.tiny import run_tiny, tiny_cell

WORKLOAD = "cancer.geo4"


def _control_cell():
    cell = tiny_cell(WORKLOAD)
    cell.config["data"]["cluster_std"] = 0.004
    cell.config["sns"].update(ingest_chunk=8192, ingest_superbatch=4,
                              log2_cols=14)
    cell.traffic.update(chunk_points=65536, fold_calls=24)
    return cell


@pytest.mark.parametrize("control", [False, True], ids=["sound", "control"])
def test_control_fails_the_sketch_limit(control):
    cell = _control_cell()
    variant = harness.driver(cell).CONTROL if control else None
    r = harness.run_cell(WORKLOAD, 2147483001, 0.01, False,
                         t_start=time.perf_counter(), require_tpu=False,
                         variant=variant, cell=cell, log=lambda m: None)
    assert r["correct"] is not control, r["checks"]
    if control:
        assert r["checks"]["sketch_err_sigma"]["value"] > \
            r["checks"]["sketch_err_sigma"]["limit"]


@pytest.mark.parametrize("fault,numbers", [
    ("merge_leaves_site_out", ("merge_gap", "points_gap")),
    ("merge_max", ("merge_gap",)),
    ("site_not_folded", ("site_points_gap", "points_gap")),
])
def test_fault_is_caught_where_it_breaks(fault, numbers):
    cell = tiny_cell(WORKLOAD)
    variant = {f.__name__: f for f in harness.driver(cell).FAULTS}[fault]
    r = run_tiny(WORKLOAD, variant=variant, cell=cell)
    for name in numbers:
        c = r["checks"][name]
        assert c["value"] > c["limit"], (name, r["checks"])


def test_sound_run_merges_every_round():
    r = run_tiny(WORKLOAD)
    assert r["correct"], r["checks"]
    assert r["window"]["merges"] == r["attempted"]
    assert r["window"]["points"] == r["attempted"] * 4 * 8192
    assert r["checks"]["merge_gap"]["value"] == 0


def test_geo_stages_names_fold_and_merge_scopes():
    """geo_stages.py finds the sites' fold and the merge scopes in the
    compiled programs a round runs, and a window of rounds with its
    per-call spans; its per-site reduction divides by each site's own
    points."""
    from snsbench import geo_stages

    out, op = geo_stages.run(WORKLOAD, 3, 0.2, False, require_tpu=False,
                             cell=tiny_cell(WORKLOAD), log=lambda m: None)
    assert out["window"]["attempted"] == out["calls"]["calls"] > 0
    assert out["program"]["counters"]["sns.geo.merges"] > 0
    found = set()
    for text in geo_stages.compiled_texts(op):
        for scopes in geo_stages.hlo_scopes(text).values():
            found |= set(scopes.values())
    assert {"sns.fold.sort", "sns.fold.scatter", "sns.geo.psum",
            "sns.geo.gather", "sns.geo.extract"} <= found
    r = {"chips": 4, "scopes": {"sns.fold.sort": 1.0, "sns.geo.psum": 0.5,
                                "other": 0.25}}
    per = geo_stages.per_site(r, 8_000_000, 10)
    assert per["sort_device_s_per_site_mpoint"] == 0.5   # 4 s / 8 Mpoint
    assert per["merge_scopes_s_per_round"] == {"sns.geo.psum": 0.05}
