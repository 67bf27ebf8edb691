"""The trace reduction on a small trace of the xplane's structure."""
import json
import os

import pytest

from snsbench import trace_reduce

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "trace_fixture.json")
NAMES = ("update", "refresh", "transform", "wait")


@pytest.fixture
def planes():
    with open(FIXTURE) as f:
        return json.load(f)


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == \
        [[1, 4], [5, 8]]


def test_busy_union_and_idle_share(planes):
    r = trace_reduce.reduce(planes, NAMES)
    assert r["window_s"] == pytest.approx(9000e-9)
    # ops clipped to the window [1000, 10000]: [1000, 3000] [4000, 4500]
    # [7000, 8000] [9500, 10000]; the XLA Modules line is not counted
    assert r["busy_s"] == pytest.approx(4000e-9)
    assert r["idle_pct"] == pytest.approx(100 * 5000 / 9000)
    assert r["chips"] == 1


def test_top_ops_are_clipped_self_times(planes):
    ops = dict(trace_reduce.reduce(planes, NAMES)["device_ops"])
    # opB [1500, 3000] overlaps opA [1000, 2000] and is taken out of it;
    # the loop [7000, 8000] holds its body op [7200, 7700]
    assert ops == pytest.approx({"opA": 1000e-9, "opB": 1500e-9,
                                 "opC": 500e-9, "while.3 s32[]": 500e-9,
                                 "fusion.7 f32[8]": 500e-9})


def test_op_name_is_the_hlo_instruction():
    assert trace_reduce.op_name(
        "%fusion.12 = f32[8]{0:T(128)} fusion(f32[8] %p), kind=kLoop") == \
        "fusion.12 f32[8]"
    assert trace_reduce.op_name(
        "%sort.3 = (s32[64]{0}, f32[64]{0}) sort(s32[64] %a)") == \
        "sort.3 s32[64]"
    assert trace_reduce.op_name("opA") == "opA"


def test_self_times_take_out_nested_events():
    iv = [(0, 10, "loop"), (1, 3, "a"), (4, 6, "b"), (6, 9, "c"),
          (7, 8, "d")]
    assert trace_reduce.self_times(iv) == [3, 2, 2, 2, 1]


def test_gaps_named_by_innermost_span(planes):
    gaps = dict(trace_reduce.reduce(planes, NAMES)["idle_gaps"])
    # [3000, 4000] and [8000, 9500] fall in update spans; [4500, 7000]
    # falls between them, in the window span only
    assert gaps == pytest.approx({"update": 2500e-9, "bench_loop": 2500e-9})


def test_no_window_or_no_device_gives_nothing(planes):
    host_only = [p for p in planes if not p["name"].startswith("/device")]
    assert trace_reduce.reduce(host_only, NAMES) is None
    no_window = [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["update", 0, 10]]}]}] + planes[1:]
    assert trace_reduce.reduce(no_window, NAMES) is None


def test_two_chips_average_busy(planes):
    second = json.loads(json.dumps(planes[1]))
    second["name"] = "/device:TPU:1"
    second["lines"][0]["events"] = [["opD", 1000, 9000]]
    r = trace_reduce.reduce(planes + [second], NAMES)
    assert r["busy_s"] == pytest.approx((4000e-9 + 9000e-9) / 2)


def test_trimmed_chip_trace():
    """200 ms of a cancer.ingest trace from a v5e (the end of one
    update() call and the start of the next), as load_xplane keeps it."""
    import numpy as np

    with open(os.path.join(os.path.dirname(FIXTURE),
                           "chip_trace_ingest.json")) as f:
        planes = json.load(f)
    r = trace_reduce.reduce(planes, NAMES)
    (w0, w1), = [(e[1], e[1] + e[2]) for e in planes[0]["lines"][0]["events"]
                 if e[0] == "bench_loop"]
    ops = [e for line in planes[1]["lines"] if line["name"] == "XLA Ops"
           for e in line["events"]]
    # an independent union: a 100 ns timeline of the window
    grid = np.zeros(int((w1 - w0) // 100) + 1, bool)
    for _, s, d in ops:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            grid[int((a - w0) // 100):int(np.ceil((b - w0) / 100))] = True
    assert r["busy_s"] == pytest.approx(grid.sum() * 100e-9, rel=2e-3)
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert {k for k, _ in r["idle_gaps"]} <= {"update", "bench_loop"}
    assert all(" " in k for k, _ in r["device_ops"])
    assert sum(v for _, v in r["device_ops"]) <= r["busy_s"] * (1 + 1e-9)
