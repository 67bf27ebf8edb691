"""Stage scopes, program spans and innermost gap naming of
``snsbench/stages.py``, beside the benchmark's own trace reduction."""
import json
import os
import pytest

from snsbench import stages, trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data")
FOLD = ("sns.fold.keys", "sns.fold.sort", "sns.fold.scatter",
        "sns.fold.merge")
FEED = ("sns.ingest.assemble", "sns.ingest.put")
OLD_KEYS = ("window_s", "busy_s", "idle_pct", "chips", "device_ops",
            "idle_gaps")

# four instructions of the fold as a v5e compiles it (backend_config cut)
HLO = """HloModule jit_ingest_superbatch, is_scheduled=true
  %fusion.298 = s32[105536]{0:T(1024)S(1)} fusion(%get-tuple-element.1022, %pad_clamp_fusion.20), kind=kCustom, calls=%fused_computation.1.clone.clone.clone, metadata={op_name="jit(ingest_superbatch)/while/body/closed_call/sns.fold.merge/jit(searchsorted)/vmap()/while/body/closed_call/gather" stack_frame_id=277}
  %sort.29 = (u32[65536]{0:T(1024)}, u32[65536]{0:T(1024)}, s32[65536]{0:T(1024)S(1)}) sort(%get-tuple-element.895, %get-tuple-element.894, %iota.115), dimensions={0}, is_stable=true, to_apply=%region_1.2, metadata={op_name="jit(ingest_superbatch)/while/body/closed_call/sns.fold.sort/jit(lexsort)/sort" stack_frame_id=81}
  %copy.57 = f32[2,2048,8,128]{3,1,2,0:T(8,128)S(1)} copy(%bitcast.318)
  ROOT %fusion.250 = f32[4194304]{0:T(1024)S(1)} fusion(%bitcast.303, %get-tuple-element.913, %get-tuple-element.914), kind=kCustom, calls=%fused_computation.51.clone.clone.clone, metadata={op_name="jit(ingest_superbatch)/while/body/closed_call/sns.fold.scatter/scatter-add" stack_frame_id=255}
  %add.4 = s32[] add(%a, %b), metadata={op_name="jit(ingest_superbatch)/while/body/add"}
"""


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def scoped():
    """One superbatch boundary of a traced cancer.ingest run on a v5e
    (233.7 ms: the end of one update() call, the host feed of the next
    and its first superbatch), as load_xplane keeps it with the op
    scopes of the compiled fold; names cut to instruction and result."""
    planes = _load("chip_trace_scopes.json")
    return planes, stages.reduce(planes, ("update",))


def test_hlo_scopes_take_the_outermost_fold_component():
    assert stages.hlo_scopes(HLO) == {"jit_ingest_superbatch": {
        "fusion.298": "sns.fold.merge", "sort.29": "sns.fold.sort",
        "fusion.250": "sns.fold.scatter"}}
    nested = HLO.replace("sns.fold.merge/jit(searchsorted)",
                         "sns.fold.merge/sns.fold.sort")
    assert stages.hlo_scopes(nested)["jit_ingest_superbatch"][
        "fusion.298"] == "sns.fold.merge"
    assert stages.hlo_scopes(HLO.replace("sns.fold.", "x.")) == {}


def test_name_scopes_by_the_module_that_ran_the_op():
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["%fusion.1 = f32[8]{0} fusion(%p)", 10, 5],
            ["%fusion.1 = f32[8]{0} fusion(%p)", 110, 5],
            ["%fusion.1 = f32[8]{0} fusion(%p)", 210, 5],
            ["%copy.2 = f32[8]{0} copy(%p)", 20, 5]]},
        {"name": "XLA Modules", "events": [
            ["jit_fold(123)", 0, 100], ["jit_other(9)", 100, 100]]}]}]
    out = stages.name_scopes(
        planes, {"jit_fold": {"fusion.1": "sns.fold.merge"}})
    assert [e[3:] for e in out[0]["lines"][0]["events"]] == \
        [["sns.fold.merge"], [], [], []]


def test_innermost_span_holds_each_point():
    spans = [("update", 0, 100), ("sns.a", 10, 20), ("sns.b", 30, 60),
             ("sns.c", 40, 50), ("sns.d", 70, 80)]
    assert stages.innermost(spans, [5, 15, 25, 45, 55, 75, 90, 150]) \
        == ["update", "sns.a", "update", "sns.c", "sns.b", "sns.d",
            "update", None]


@pytest.mark.parametrize("fixture", ["trace_fixture.json",
                                     "chip_trace_ingest.json",
                                     "chip_trace_scopes.json"])
def test_old_keys_as_the_benchmark_gives_them(fixture):
    """The benchmark's keys are the benchmark's reduction, unchanged
    (recorded from it), whether or not the trace has program spans or
    scopes."""
    names = ("update", "refresh", "transform", "wait")
    before = _load("reduce_before_scopes.json")[fixture]
    r = stages.reduce(_load(fixture), names)
    assert {k: r[k] for k in OLD_KEYS} == before
    assert {k: r[k] for k in OLD_KEYS} == trace_reduce.reduce(
        _load(fixture), names)


@pytest.mark.parametrize("fixture", ["trace_fixture.json",
                                     "chip_trace_ingest.json"])
def test_without_program_spans_or_scopes(fixture):
    """Spans that do not nest name the idle as the benchmark does, and
    every op is ``other``."""
    r = stages.reduce(_load(fixture), ("update", "refresh", "transform",
                                       "wait"))
    assert r["host_spans"] == {}
    assert set(r["scopes"]) == {"other"}
    assert r["idle_spans"] == pytest.approx(dict(r["idle_gaps"]))
    assert r["scoped_ops"] == r["device_ops"]


def test_scopes_hold_the_fold(scoped):
    _, r = scoped
    assert set(FOLD) <= set(r["scopes"])
    assert sum(r["scopes"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    assert r["scopes"]["other"] <= 0.01 * r["busy_s"]
    assert r["scopes"]["sns.fold.merge"] == max(r["scopes"].values())
    names = [k for k, _ in r["scoped_ops"]]
    assert names[0] == "fusion.298 s32[105536] sns.fold.merge"
    assert all(k.split()[-1] in FOLD for k in names)
    assert [v for _, v in r["scoped_ops"]] == \
        [v for _, v in r["device_ops"]]


def test_host_spans_and_innermost_gap_names(scoped):
    planes, r = scoped
    assert set(r["host_spans"]) == set(FEED) | {"sns.ingest.dispatch",
                                                "sns.update.wait"}
    # self times inside the window: the feed spans lie wholly inside it
    events = planes[0]["lines"][0]["events"]
    put = sum(e[2] for e in events if e[0] == "sns.ingest.put") * 1e-9
    assert r["host_spans"]["sns.ingest.put"] == pytest.approx(put)
    idle = r["idle_spans"]
    assert sum(idle.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    assert sum(idle.values()) == pytest.approx(
        sum(v for _, v in r["idle_gaps"]), rel=1e-9)
    # the gap between the calls falls in the next call's first assembly
    assert max(idle, key=idle.get) == "sns.ingest.assemble"
    assert idle.get("update", 0.0) + idle.get("bench_loop", 0.0) < \
        0.25 * sum(idle.values())


def test_per_stage_numbers(scoped):
    _, r = scoped
    out = stages.per_stage(r, 10 ** 6)
    for scope in FOLD:
        assert out[scope.split(".")[-1] + "_device_s_per_mpoint"] == \
            pytest.approx(r["scopes"][scope])
    assert out["feed_host_s_per_mpoint"] == pytest.approx(
        sum(r["host_spans"][k] for k in FEED))
    idle_pct = sum(r["idle_spans"].values()) / r["window_s"] * 100
    assert 0 < out["feed_idle_pct"] <= idle_pct + 1e-9
    assert idle_pct == pytest.approx(r["idle_pct"], rel=1e-6)
    assert 99 <= out["scoped_pct_of_busy"] <= 100


class _Driver:
    """Calls of 1, 5 and 2 s, each waiting all but 0.5 s of it."""

    def __init__(self):
        self.totals = {"sns.update.wait": 0.0, "sns.ingest.put": 0.0}
        self.clock = iter([1.0, 5.0, 2.0])

    def feed(self):
        t = next(self.clock)
        self.totals["sns.update.wait"] += t - 0.5
        self.totals["sns.ingest.put"] += 0.5

    def snapshot(self):
        return {"spans": {k: {"seconds": v} for k, v in self.totals.items()}}


def test_call_spans_name_where_the_slowest_call_waited(monkeypatch):
    op = _Driver()
    calls = stages.CallSpans(op, op.snapshot)
    walls = iter([0.0, 1.0, 10.0, 15.0, 20.0, 22.0])
    monkeypatch.setattr(stages.time, "perf_counter", lambda: next(walls))
    for _ in range(3):
        op.feed()
    out = calls.summary()
    assert out["calls"] == 3 and out["slow_call_at"] == 1
    assert out["slow_call_s"] == 5.0 and out["call_s_median"] == 2.0
    assert out["slow_call_wait_s"] == 4.5
    assert out["call_wait_s_median"] == 1.5
    assert out["slow_call_put_s"] == 0.5
    assert out["slow_call_assemble_s"] == 0.0
    assert out["slow_call_dispatch_s"] == 0.0


def test_run_on_a_tiny_cell():
    """The whole run on the CPU: per-call span seconds of the window, and
    the four stage scopes in the compiled fold the driver's service runs
    (no TPU plane, so no reduced trace)."""
    from snsbench.tests import tiny

    out, op = stages.run("cancer.ingest", 3, 0.5, True, require_tpu=False,
                         cell=tiny.tiny_cell("cancer.ingest"),
                         log=lambda m: None)
    calls = out["calls"]
    assert calls["calls"] == out["window"]["attempted"] >= 1
    assert calls["slow_call_s"] >= calls["call_s_median"] > 0
    assert calls["slow_call_put_s"] > 0 and calls["slow_call_wait_s"] > 0
    assert "trace" not in out
    (scopes,) = stages.hlo_scopes(stages.compiled_text(op)).values()
    assert set(scopes.values()) == set(FOLD)
