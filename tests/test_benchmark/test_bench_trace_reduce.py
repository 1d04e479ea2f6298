"""The trace reduction on a small hand-built trace: overlapping operations, a
gap, two executables, a second device, the program's host spans (one inside
another), and an execution that the trace's edge cut."""

import pytest

from benchmarks.chip.trace_reduce import merge_intervals, reduce_trace

MS = 1_000_000


def _trace(devices=1):
    ops = [("reshape.0", -1 * MS, 1 * MS), ("fusion.1", 0, 4 * MS), ("fusion.2", 2 * MS, 4 * MS), ("copy.3", 10 * MS, 2 * MS),
           ("fusion.1", 12 * MS, 8 * MS), ("reshape.0", 20 * MS, 1 * MS)]
    modules = [("jit_train_step(123)", 0, 7 * MS), ("jit__gather_all(9)", 10 * MS, 2 * MS), ("jit_train_step(123)", 12 * MS, 8 * MS)]
    planes = [
        {"name": f"/device:TPU:{i}", "lines": [{"name": "XLA Ops", "events": ops}, {"name": "XLA Modules", "events": modules},
                                               {"name": "Steps", "events": [("0", 0, 20 * MS)]}]}
        for i in range(devices)
    ]
    planes.append({"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ("sheeprl/rollout", 0, 20 * MS), ("sheeprl/rollout/action-fetch", 5 * MS, 4 * MS), ("something_else", 0, 20 * MS)]}]})
    return {"planes": planes}


def test_merge_intervals():
    assert merge_intervals([(5, 7), (0, 2), (1, 3), (7, 9), (4, 4)]) == [(0, 3), (5, 9)]


@pytest.mark.parametrize("devices", [1, 4])
def test_busy_idle_and_the_executable(devices):
    out = reduce_trace(_trace(devices))
    assert out["window_s"] == pytest.approx(0.022)
    assert out["busy_s"] == pytest.approx(0.018)  # -1-6 overlapping, 10-21; the gap is 6-10
    assert out["idle_pct"] == pytest.approx(100 * 4 / 22)
    assert out["module_runs"] == 2 * devices
    assert out["module_device_ms"] == pytest.approx((6 + 8) / 2)  # busy inside the two train_step spans
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.012)]
    # the gap goes to the innermost span over each piece of it
    assert out["idle_by_span"] == [["sheeprl/rollout/action-fetch", pytest.approx(0.003)], ["sheeprl/rollout", pytest.approx(0.001)]]
    assert out["longest_gap_ms"][0] == pytest.approx(4.0)


def test_an_execution_at_the_traces_edge_is_left_out():
    """The trace may have cut it there: half a train step read as a whole one."""
    trace = _trace()
    for line in trace["planes"][0]["lines"]:
        if line["name"] == "XLA Ops":
            line["events"] = line["events"][1:]  # the trace now starts inside the first train step
    out = reduce_trace(trace)
    assert out["module_runs"] == 1 and out["module_device_ms"] == pytest.approx(8.0)
    out = reduce_trace(trace, module_match="jit__gather_all")
    assert out["module_runs"] == 1 and out["module_device_ms"] == pytest.approx(2.0)


def test_an_unattributed_gap_is_listed_as_such():
    trace = _trace()
    trace["planes"][-1]["lines"][0]["events"] = []
    assert reduce_trace(trace)["idle_by_span"] == [["unattributed", pytest.approx(0.004)]]


def test_an_executable_that_never_ran_reads_nothing_not_zero():
    out = reduce_trace(_trace(), module_match="no_such_step")
    assert out["module_runs"] == 0 and out["module_device_ms"] is None


def test_a_trace_without_a_device_is_refused():
    with pytest.raises(ValueError):
        reduce_trace({"planes": [{"name": "/host:CPU", "lines": []}]})
    with pytest.raises(ValueError):
        reduce_trace({"planes": [{"name": "/device:TPU:0", "lines": [{"name": "Steps", "events": []}]}]})


def test_long_operation_names_are_cut():
    from benchmarks.chip.trace_reduce import short_name

    long = "%copy.17 = u8[250000,1,3,64,64]{4,3,2,1,0:T(8,128)(4,1)} copy(u8[250000,1,3,64,64]{0,4,3,2,1} %buf), sharding={replicated}"
    assert short_name(long).startswith("%copy.17 u8[250000,1,3,64,64]") and len(short_name(long)) <= 96
    assert short_name("fusion.1") == "fusion.1"

