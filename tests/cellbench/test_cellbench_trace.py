"""The reduction from a trace to numbers: on a hand-made trace whose answers
are known, and on a small trace recorded on the chip."""

from __future__ import annotations

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from cellbench import trace_reduce as tr  # noqa: E402
from cellbench.readers import device_trace  # noqa: E402

LAY = tr.layout()
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "recorded_trace.json.gz")


def hand_made() -> dict:
    """One device, 1000 ns window: two decode_multi executions and one
    prefill; operations with a 20 ns overlap and idle gaps between them."""
    ops = [
        ["%copy.1 = bf16[1,8,3400,16,128]{4,3,2,1,0} copy(%p)", 0.0, 100.0],
        ["%fusion.7 = bf16[64,14336]{1,0} fusion(%a)", 100.0, 100.0],
        ["%custom-call.3 = bf16[64,8,4,128]{3,2,1,0} custom-call(%q)", 180.0, 120.0],  # overlaps 20
        ["%slice.2 = bf16[1,8,3400,16,128]{4,3,2,1,0} slice(%c)", 400.0, 100.0],
        ["%custom-call.3 = bf16[64,8,4,128]{3,2,1,0} custom-call(%q)", 500.0, 50.0],
        ["%fusion.9 = f32[512]{0} fusion(%b)", 700.0, 200.0],
        ["%zero.1 = f32[] constant()", 950.0, 0.0],
    ]
    # the device names a program by a hash; the runner method called last
    # before an execution started is the one that launched it
    modules = [
        ["jit__unknown(123)", 1.0, 299.0],
        ["jit__unknown(123)", 400.0, 160.0],
        ["jit__lambda(7)", 565.0, 1.0],
        ["jit__unknown(9)", 690.0, 310.0],  # runs into the trace's end
    ]
    frames = [
        ["$engine.py:1 _engine_loop", -50.0, 1050.0],
        ["$model_runner.py:1648 decode_multi", -10.0, 5.0],
        ["$model_runner.py:1648 decode_multi", 390.0, 5.0],
        ["$model_runner.py:1400 fetch_sample", 562.0, 5.0],
        ["$model_runner.py:1300 prefill_packed_arrays", 680.0, 5.0],
        ["$model_runner.py:983 mixed_step", 290.0, 120.0],
        ["$base_events.py:1922 _run_once", 540.0, 170.0],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": frames}]},
    ]}


def ctx(trace):
    return {"trace": tr.reduce_device(trace, LAY), "facts": {"decode_horizon": 4,
            "device_kind": "TPU v5 lite"}, "client": {}, "config": {}}


def test_busy_is_a_union_and_idle_the_rest():
    red = tr.reduce_device(hand_made(), LAY)
    # [0,300) + [400,550) + [700,900) = 650 of a 1000 ns window
    assert red["busy_s"] == pytest.approx(650e-9)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert device_trace.read(ctx(hand_made()), {"kind": "idle_share"}) == pytest.approx(35.0)


def test_operation_classes_come_from_the_metric_s_own_patterns():
    classes = ["^copy", "^slice", "dynamic-update-slice"]
    got = device_trace.read(ctx(hand_made()), {"kind": "class_share", "classes": classes})
    # copy 100 + slice 100 of 670 summed operation time
    assert got == pytest.approx(100 * 200 / 670)
    assert tr.base_name("%copy_dynamic-update-slice_fusion.12 = x") == "copy_dynamic-update-slice_fusion"
    assert tr.base_name("fusion.123") == "fusion"


def test_per_dispatch_grouping():
    c = ctx(hand_made())
    params = {"kind": "module_busy_ms_per_step", "program": "decode_multi"}
    # operations that start inside the two decode_multi executions ([1,300)
    # and [400,560)): busy [100,300) and [400,550), 350 ns, 2 calls x 4 steps
    assert device_trace.read(c, params) == pytest.approx(350e-6 / 8)
    kernel = {"kind": "class_ms_per_step", "program": "decode_multi",
              "classes": ["custom-call"]}
    assert device_trace.read(c, kernel) == pytest.approx(170e-6 / 8)
    none = {"kind": "module_busy_ms_per_step", "program": "no_such_program"}
    assert device_trace.read(c, none) is None


def test_a_run_without_a_trace_gives_nothing():
    assert device_trace.read({"trace": None}, {"kind": "idle_share"}) is None
    empty = {"planes": [{"name": "/host:CPU", "lines": []}]}
    assert device_trace.read(ctx(empty), {"kind": "idle_share"}) is None


def test_breakdown_names_operations_and_gives_gaps_to_frames():
    trace = hand_made()
    red = tr.reduce_device(trace, LAY)
    top = tr.top_ops(red)
    assert top[0] == ["fusion_f32_512", pytest.approx(200e-9)]
    assert ["custom-call_bf16_64_8_4_128", pytest.approx(170e-9)] in top
    assert all(len(t) == 2 for t in top) and len(top) <= 10
    gaps = dict(tr.idle_gaps(red, trace, LAY))
    # gaps: [300,400) under mixed_step; [550,700) under _run_once; [900,1000) loop only
    assert gaps["_model_runner.py_983_mixed_step"] == pytest.approx(100e-9)
    assert gaps["_base_events.py_1922__run_once"] == pytest.approx(150e-9)
    assert gaps["_engine.py_1__engine_loop"] == pytest.approx(100e-9)


def test_roofline_is_needed_time_over_device_time():
    import json

    with open(os.path.join(REPO, "cellbench", "configs", "mistral7b-int8.json")) as f:
        config = json.load(f)
    c = ctx(hand_made())
    c["config"] = config
    c["client"] = {"live": {"lanes": 34.0, "context": 450.0}}
    params = {"kind": "roofline", "program": "decode_multi"}
    device_ms = 350e-6 / 8
    want = 100 * (9_120_530_432 / 819e9 * 1e3) / device_ms
    assert device_trace.read(c, params) == pytest.approx(want)
    assert c["notes"]["roofline"]["bound"] == "bytes"
    c["facts"]["device_kind"] = "unknown chip"
    with pytest.raises(KeyError):
        device_trace.read(c, params)


def test_executions_are_given_to_the_method_that_launched_them():
    red = tr.reduce_device(hand_made(), LAY)
    names = [m[0] for m in red["planes"][0]["modules"]]
    # the tiny conversion program after the second dispatch still counts
    # as decode_multi's: nothing else was launched in between
    assert names == ["decode_multi", "decode_multi", "decode_multi", "prefill_packed_arrays"]
    assert [m[3] for m in red["planes"][0]["modules"]] == [0, 2, 2, 3]  # call 1 is the mixed_step
    assert tr.modules_named(red["planes"][0], "prefill_packed_arrays") == ([], 0)
    assert tr.slice_trace(hand_made(), 400.0, 600.0)["planes"][0]["lines"][1]["events"] == [
        ["jit__unknown(123)", 400.0, 160.0], ["jit__lambda(7)", 565.0, 1.0]]


def test_merge_and_membership():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    ops = [["a", 1.0, 1.0], ["b", 10.0, 1.0], ["c", 20.0, 1.0]]
    assert tr.in_modules(ops, [["m", 0.0, 5.0], ["m", 19.0, 5.0]]) == [ops[0], ops[2]]


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace kept")
def test_recorded_trace_reduces_to_what_the_chip_run_read():
    """A slice of a trace recorded on the chip (mistral7b-int8.chat-steady):
    the layout's names find the device plane, its operations and programs,
    and the numbers are the ones that run read from the same slice."""
    import json

    trace = tr.load(RECORDED)
    with open(RECORDED.replace(".json.gz", ".expected.json")) as f:
        want = json.load(f)
    red = tr.reduce_device(trace, LAY)
    assert len(red["planes"]) == 1
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]
    c = ctx(trace)
    for name, params in want["metrics"].items():
        got = device_trace.read(c, params["params"])
        assert got == pytest.approx(params["value"], rel=1e-9), name
    assert [n for n, _ in tr.top_ops(red)][:3] == want["top_ops"][:3]
    assert [[m[0], m[3]] for m in red["planes"][0]["modules"]] == want["modules"]
    # one whole decode_multi execution of four steps, about 136 ms each, most
    # of it copies of the cache pool; the Pallas kernel about a quarter
    assert 120 < want["metrics"]["decode_device_ms"]["value"] < 150
    assert want["metrics"]["copy_share"]["value"] > 50
    gaps = dict(tr.idle_gaps(red, trace, LAY))
    assert gaps and sum(gaps.values()) <= red["window_s"] - red["busy_s"] + 1e-9
