"""The readers of the program's own phases: `host_phases` on two hand-made
ledgers, `host_device_join` on a hand-made trace whose overlaps are known
(and on a copy whose host clock is shifted), on a slice of a chip trace kept
with what the chip run read from it, and through `--cpu-rehearsal`."""

from __future__ import annotations

import gzip
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from cellbench import manifest  # noqa: E402
from cellbench.readers import host_device_join as hdj  # noqa: E402
from cellbench.readers import host_phases  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW_METRICS = (
    "frontend_pre_ms", "frontend_post_ms_per_ktok", "queue_wait_ms", "prefill_wait_ms",
    "loop_host_ms_per_dispatch", "idle_packer_share", "idle_frontend_share",
    "idle_dispatch_share", "attn_kernel_roofline", "first_dispatch_s",
)


def params(name: str) -> dict:
    return manifest.load_json("cellbench", "metrics", name + ".json")["params"]


# ------------------------------------------------------------ host_phases


def ledgers():
    def row(count, ms, self_ms=None):
        return {"count": count, "ms": ms, "self_ms": ms if self_ms is None else self_ms}

    a = {
        "decode_tokens": 1000, "compile_s_by_label": {"decode_multi@H4B64": 118.0, "prefill_packed": 9.5},
        "phases": {
            "frontend.parse": row(10, 2.0), "frontend.preprocess": row(10, 5.0),
            "frontend.detokenize": row(1000, 60.0), "frontend.sse": row(3000, 140.0),
            "queue_wait": row(10, 900.0), "prefill_wait": row(9, 1800.0),
            "loop.iter": row(20, 11000.0, 40.0), "loop.reap": row(20, 1.0),
            "loop.admit": row(20, 900.0, 10.0), "loop.pack": row(25, 30.0),
            "loop.dispatch": row(25, 10000.0), "loop.emit": row(25, 100.0),
            "loop.stats": row(20, 9.0),
        },
    }
    b = {
        "decode_tokens": 3000, "compile_s_by_label": {**a["compile_s_by_label"], "prefill_chunk": 7.0},
        "phases": {
            "frontend.parse": row(30, 8.0), "frontend.preprocess": row(30, 21.0),
            "frontend.detokenize": row(3000, 200.0), "frontend.sse": row(9000, 500.0),
            "queue_wait": row(30, 6900.0), "prefill_wait": row(29, 9800.0),
            "loop.iter": row(120, 62000.0, 140.0), "loop.reap": row(120, 3.0),
            "loop.admit": row(120, 5900.0, 60.0), "loop.pack": row(125, 130.0),
            "loop.dispatch": row(125, 60000.0), "loop.emit": row(125, 700.0),
            "loop.stats": row(120, 59.0),
            "loop.yield": row(80, 2000.0),  # new in the window: counted from nothing
        },
    }
    return a, b


def test_host_phases_reads_differences_of_the_two_ledgers():
    a, b = ledgers()
    ctx = {"ledger0": a, "ledger1": b, "notes": {}}
    read = lambda name: host_phases.read(ctx, params(name))  # noqa: E731
    # (6 + 16) ms over 20 requests
    assert read("frontend_pre_ms") == pytest.approx(22.0 / 20)
    # (140 + 360) ms over 2,000 tokens
    assert read("frontend_post_ms_per_ktok") == pytest.approx(250.0)
    assert read("queue_wait_ms") == pytest.approx(6000.0 / 20)
    assert read("prefill_wait_ms") == pytest.approx(8000.0 / 20)
    # self ms of reap 2, admit 50, pack 100, emit 600, stats 50 over 100 dispatches
    assert read("loop_host_ms_per_dispatch") == pytest.approx(802.0 / 100)
    # the sum at the window's OPENING: what set-up paid
    assert read("first_dispatch_s") == pytest.approx(127.5)
    assert ctx["notes"]["phases"]["loop.yield"] == {"count": 80, "ms": 2000.0, "self_ms": 2000.0}
    assert ctx["notes"]["phases"]["loop.dispatch"]["count"] == 100


def test_host_phases_gives_nothing_without_the_table():
    """The parent of the PR that brought the table: no `phases` in the
    ledger. Nothing is returned and nothing raises; the first dispatches,
    which the ledger always had, are still read."""
    a, b = ledgers()
    del a["phases"], b["phases"]
    ctx = {"ledger0": a, "ledger1": b, "notes": {}}
    for name in ("frontend_pre_ms", "frontend_post_ms_per_ktok", "queue_wait_ms",
                 "prefill_wait_ms", "loop_host_ms_per_dispatch"):
        assert host_phases.read(ctx, params(name)) is None
    assert host_phases.read(ctx, params("first_dispatch_s")) == pytest.approx(127.5)
    assert host_phases.read({}, params("queue_wait_ms")) is None
    # a phase that never ran in the window gives nothing, not a division
    a, b = ledgers()
    b["phases"]["queue_wait"] = dict(a["phases"]["queue_wait"])
    assert host_phases.read({"ledger0": a, "ledger1": b}, params("queue_wait_ms")) is None


# -------------------------------------------------------- host_device_join


def hand_made():
    """One device, a window of 1000 ns, busy 100-400 and 520-900; the loop:
    pack 60-100, dispatch 100-500 whose runner.call ends at 430, emit
    500-515, stats 515-520, dispatch 520-950 (runner.call to 930), yield
    950-980, and nothing from 980 on.

    Idle: 0-100 (60 of it before any phase, 40 in pack), 400-430 (dispatch,
    before the call's end), 430-500 (dispatch, after it: the event loop),
    500-520 (emit and stats), 900-930 (dispatch), 930-950 (after the call),
    950-980 (yield), 980-1000 (nothing)."""
    planes = [{"span": [0.0, 1000.0], "busy": [[100.0, 400.0], [520.0, 900.0]],
               "starts": [105.0, 525.0]}]
    ann = [
        ["loop.iter", 50.0, 935.0, {}],
        ["loop.admit", 55.0, 3.0, {}],
        ["loop.pack", 60.0, 40.0, {}],
        ["loop.dispatch", 100.0, 400.0, {"label": "decode_multi@H4B64", "lanes": 40, "ctx_tokens": 16000, "horizon": 4}],
        ["runner.call", 102.0, 328.0, {"label": "decode_multi@H4B64"}],
        ["loop.emit", 500.0, 15.0, {}],
        ["loop.stats", 515.0, 5.0, {}],
        ["loop.dispatch", 520.0, 430.0, {"label": "decode_multi@H4B64", "lanes": 20, "ctx_tokens": 9000, "horizon": 4}],
        ["runner.call", 521.0, 409.0, {"label": "decode_multi@H4B64"}],
        ["loop.yield", 950.0, 30.0, {}],
        ["loop.dispatch", 10.0, 20.0, {"label": "prefill_packed", "lanes": 0, "ctx_tokens": 0}],
        ["frontend.sse", 440.0, 30.0, {}],  # another task, inside the dispatch's wait
    ]
    return planes, ann


def test_interval_arithmetic():
    a = [(0.0, 10.0), (20.0, 30.0)]
    b = [(5.0, 22.0), (25.0, 26.0), (29.0, 40.0)]
    assert hdj.overlap(a, b) == pytest.approx(5 + 2 + 1 + 1)
    assert hdj.subtract(a, b) == [(0.0, 5.0), (22.0, 25.0), (26.0, 29.0)]
    assert hdj.subtract(a, []) == a and hdj.subtract([], b) == []


def test_join_splits_the_idle_time_by_where_the_loop_was():
    planes, ann = hand_made()
    out = hdj.join(planes, ann)
    idle = out["idle"]
    assert idle["all"] == pytest.approx(32.0)  # 320 of 1000 ns
    # the first, 20 ns dispatch has no runner.call inside it: all of it is
    # dispatch (10-30), with 400-430 and 900-930
    assert idle["dispatch"] == pytest.approx((20 + 30 + 30) / 10)
    assert idle["frontend"] == pytest.approx((70 + 20 + 30) / 10)
    # admit 55-58, pack 60-100, emit and stats 500-520
    assert idle["packer"] == pytest.approx((3 + 40 + 20) / 10)
    assert idle["unattributed"] == pytest.approx((320 - 80 - 120 - 63) / 10)
    assert idle["dispatch"] + idle["frontend"] + idle["packer"] + idle["unattributed"] == pytest.approx(idle["all"])
    assert out["clock_share"] == 1.0 and out["clock_ok"] and out["executions_checked"] == 2
    live = hdj.live_lanes_context(ann, "decode_multi")
    assert live["dispatches"] == 2 and live["lanes"] == pytest.approx(30.0)
    # (16000 + 9000) tokens and 1.5 steps' growth on 60 lanes, over 60 lanes
    assert live["context"] == pytest.approx((25000 + 60 * 1.5) / 60)


def test_a_shifted_host_clock_fails_the_check_and_withholds_every_metric():
    planes, ann = hand_made()
    shifted = [[n, s + 450.0, d, at] for n, s, d, at in ann]
    out = hdj.join(planes, shifted)
    # the executions at 105 and 525 no longer begin inside a runner.call
    assert out["clock_share"] <= 0.5 and not out["clock_ok"]
    ctx = {"_host_device_join": out, "notes": {}}
    for name in ("idle_packer_share", "idle_frontend_share", "idle_dispatch_share",
                 "attn_kernel_roofline"):
        assert hdj.read(ctx, params(name)) is None


def test_an_execution_after_the_last_annotation_is_not_checked():
    """The profiler's tracers outlive the program's switch by a moment: a
    dispatch launched then is in the trace without its annotations. It is
    idle under no phase, and no evidence about the clocks."""
    planes, ann = hand_made()
    planes[0]["span"][1] = 1400.0
    planes[0]["busy"].append([1010.0, 1390.0])
    planes[0]["starts"].append(1010.0)
    out = hdj.join(planes, ann)
    assert out["executions_checked"] == 2 and out["clock_share"] == 1.0
    # 37 ns before the first pack, 980-1010 and 1390-1400
    assert out["idle"]["unattributed"] == pytest.approx(100 * (37 + 30 + 10) / 1400)


def test_join_gives_nothing_where_there_is_nothing_to_join():
    planes, ann = hand_made()
    # the parent's trace: its dispatch label is there, no `dyn:` annotation
    assert hdj.join(planes, []) is None
    assert hdj.join(planes, [a for a in ann if a[0] != "runner.call"]) is None
    # the CPU rehearsal: no device plane
    assert hdj.join([], ann) is None
    assert hdj.read({"trace": {"planes": []}, "notes": {}}, params("idle_packer_share")) is None
    assert hdj.read({"trace": None, "notes": {}}, params("attn_kernel_roofline")) is None


def test_a_program_without_the_phase_table_has_its_trace_left_alone(monkeypatch, tmp_path):
    """The parent of the PR that brought the phases, run with these files
    laid over it: its ledger has no `phases`, so it wrote no `dyn:`
    annotation, and the reader does not open its trace a second time."""
    def opened(*_a, **_k):
        raise AssertionError("the trace was opened")

    monkeypatch.setattr(hdj, "load_annotations", opened)
    monkeypatch.setattr(hdj, "profile_dir", lambda: str(tmp_path))
    planes, _ = hand_made()
    red = {"planes": [{"span": p["span"], "busy": p["busy"]} for p in planes]}
    a, b = ledgers()
    del a["phases"], b["phases"]
    ctx = {"trace": red, "ledger0": a, "ledger1": b, "notes": {}}
    for name in ("idle_packer_share", "idle_frontend_share", "idle_dispatch_share",
                 "attn_kernel_roofline"):
        assert hdj.read(ctx, params(name)) is None
    assert ctx["notes"] == {}


def test_reader_reads_the_shares_from_the_join():
    planes, ann = hand_made()
    out = hdj.join(planes, ann)
    out["live"] = hdj.live_lanes_context(ann, "decode_multi")
    ctx = {"_host_device_join": out, "notes": {}}
    assert hdj.read(ctx, params("idle_dispatch_share")) == pytest.approx(8.0)
    assert hdj.read(ctx, params("idle_frontend_share")) == pytest.approx(12.0)
    assert hdj.read(ctx, params("idle_packer_share")) == pytest.approx(6.3)


def test_profile_dir_is_found_from_the_command_line(monkeypatch):
    monkeypatch.setattr(sys, "argv", ["cellbench/run.py", "--workload", "a.b", "--seed", "1"])
    assert hdj.profile_dir() == os.path.join(REPO, "cellbench_out", "a.b", "profile")
    monkeypatch.setattr(sys, "argv", ["cellbench/run.py", "--workload=c.d"])
    assert hdj.profile_dir() == os.path.join(REPO, "cellbench_out", "c.d", "profile")
    monkeypatch.setattr(sys, "argv", ["pytest"])
    assert hdj.profile_dir() is None


def test_cut_keeps_what_lies_inside():
    planes, ann = hand_made()
    piece = hdj.cut(planes, ann, 450.0, 960.0)
    assert piece["planes"][0]["span"] == [450.0, 960.0]
    assert piece["planes"][0]["busy"] == [[520.0, 900.0]]
    assert piece["planes"][0]["starts"] == [525.0]
    # what reaches over an edge is cut there
    assert [a[:3] for a in piece["annotations"]] == [
        ["loop.iter", 450.0, 510.0], ["loop.dispatch", 450.0, 50.0],
        ["loop.emit", 500.0, 15.0], ["loop.stats", 515.0, 5.0],
        ["loop.dispatch", 520.0, 430.0], ["runner.call", 521.0, 409.0],
        ["loop.yield", 950.0, 10.0], ["frontend.sse", 450.0, 20.0],
    ]
    # and the piece reads like the whole over its range: idle 450-520 and
    # 900-960 of 510 ns
    read = hdj.join(piece["planes"], piece["annotations"])["idle"]
    assert read["all"] == pytest.approx(100 * 130 / 510)
    assert read["packer"] == pytest.approx(100 * 20 / 510)
    assert read["unattributed"] == pytest.approx(0.0)


# ------------------------------------------------- a slice of a chip trace


@pytest.mark.parametrize("cell", ["mistral7b-int8.chat-steady", "qwen25-7b-int8.chat-sat"])
def test_slice_of_the_chip_trace_reads_what_the_chip_run_read(cell):
    """Two seconds of this PR's own traced chip runs (busy union,
    the checked executions' starts, every `dyn:` annotation) with what the
    chip run's own code read from it."""
    with gzip.open(os.path.join(DATA, f"join_slice.{cell}.json.gz"), "rt") as f:
        piece = json.load(f)
    out = hdj.join(piece["planes"], piece["annotations"])
    want = piece["read"]
    for part in ("all", "dispatch", "frontend", "packer", "unattributed"):
        assert out["idle"][part] == pytest.approx(want["idle"][part], rel=1e-9, abs=1e-9)
    assert out["clock_share"] == want["clock_share"] >= 0.95
    assert out["executions_checked"] == want["executions_checked"] > 0
    idle = out["idle"]
    assert idle["dispatch"] + idle["frontend"] + idle["packer"] + idle["unattributed"] == pytest.approx(idle["all"])
    # the loop's phases name nearly all of the device's idle time
    assert idle["unattributed"] < 1.0 < idle["dispatch"]
    live = hdj.live_lanes_context(piece["annotations"], "decode_multi")
    assert live == pytest.approx(piece["live"])
    assert 1 <= live["lanes"] <= 64 and 16 <= live["context"] <= 4096
    names = {a[0] for a in piece["annotations"]}
    assert {"loop.iter", "loop.pack", "loop.dispatch", "runner.call", "loop.emit",
            "loop.stats", "frontend.detokenize", "frontend.sse"} <= names


# ----------------------------------------------------------- the manifest


def test_new_metrics_resolve_and_are_declared():
    bench = manifest.load_json("BENCHMARK.json")
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        m = manifest.load_json("cellbench", "metrics", name + ".json")
        entry = declared[name]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert m[key] == entry[key], (name, key)
        assert hasattr(manifest.reader(m["reader"]), "read")
    assert declared["prefill_wait_ms"]["workloads"] == ["mistral7b-int8.chat-steady"]
    steady = {m["name"] for m in manifest.Cell("mistral7b-int8.chat-steady").metrics("per_layer")}
    sat = {m["name"] for m in manifest.Cell("qwen25-7b-int8.chat-sat").metrics("per_layer")}
    assert set(NEW_METRICS) <= steady
    assert set(NEW_METRICS) - sat == {"prefill_wait_ms"}


@pytest.mark.timeout(600)
def test_cpu_rehearsal_traced_ends_with_its_line_and_the_phase_metrics():
    """`--cpu-rehearsal --trace 1` still ends with its result line: the
    program's table is read, and the join, which has no device plane to
    join to, gives nothing and breaks nothing."""
    import subprocess

    cp = subprocess.run(
        [sys.executable, os.path.join(REPO, "cellbench", "run.py"), "--workload",
         "qwen25-7b-int8.chat-sat", "--seed", "2147483900", "--seconds", "6", "--trace", "1",
         "--cpu-rehearsal"],
        capture_output=True, text=True, timeout=500, cwd=REPO,
    )
    assert cp.returncode == 0, cp.stderr[-3000:] + cp.stdout[-2000:]
    lines = [json.loads(l) for l in cp.stdout.splitlines() if l.startswith("{")]
    line, window = lines[-1], lines[-2]
    assert line["rehearsal"] is True and line["correct"] is True and line["failed"] == 0
    for name in ("frontend_pre_ms", "frontend_post_ms_per_ktok", "queue_wait_ms",
                 "loop_host_ms_per_dispatch", "first_dispatch_s"):
        assert line["metrics"][name]["value"] > 0, name
    for name in ("idle_packer_share", "idle_frontend_share", "idle_dispatch_share",
                 "attn_kernel_roofline", "prefill_wait_ms"):
        assert name not in line["metrics"]
    phases = window["notes"]["phases"]
    assert phases["loop.dispatch"]["count"] == phases["runner.call"]["count"] > 0
    assert "host_device_join_error" not in window["notes"]
