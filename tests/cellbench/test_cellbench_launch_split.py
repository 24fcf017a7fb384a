"""`cellbench/readers/launch_split.py`: the launch of a dispatch split where
the program splits it. On a hand-made timeline whose gaps are known; on
operations that reach over a phase's edge; on two devices; on slices of this
PR's traced chip runs kept with what the chip run read from them; on the
parent's slices, which have none of the three annotations; on the ledger of
the toy engine, as the CPU rehearsal reads it; and through the manifest."""

from __future__ import annotations

import glob
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
from cellbench.readers import launch_split as ls  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SHARES = {
    "idle_hop_share": "hop", "idle_upload_share": "upload", "idle_enqueue_share": "enqueue",
    "idle_fetch_lead_share": "fetch_lead", "idle_fetch_drain_share": "fetch_drain",
}
NEW_METRICS = (*SHARES, "launch_upload_ms", "launch_enqueue_ms", "upload_arrays_per_dispatch")


def params(name: str) -> dict:
    return manifest.load_json("cellbench", "metrics", name + ".json")["params"]


def hand_made():
    """One device, a window of 1000 ns. Dispatch A 60-500: its call 70-430
    (upload 72-82, enqueue 84-94, fetch 95-425), the device busy 100-250 and
    260-400. Dispatch B 520-950: its call 540-930 (upload 541-551, enqueue
    552-560, fetch 560-928), busy 555-900: it begins inside the enqueue and
    reaches into the fetch. A third, 10-30, has no call in the trace.

    Idle inside `loop.dispatch` up to its call's end, by part:
    hop 10-30, 60-70, 520-540 = 50; upload 72-82, 541-551 = 20; enqueue
    84-94, 552-555 = 13; fetch lead 95-100 = 5 (B's has none: the device is
    busy when it begins); mid 250-260 = 10; drain 400-425, 900-928 = 53;
    the call's rest 70-72, 82-84, 94-95, 425-430, 540-541, 551-552, 928-930
    = 14. 165 ns in all."""
    planes = [{"span": [0.0, 1000.0], "busy": [[100.0, 250.0], [260.0, 400.0], [555.0, 900.0]],
               "starts": [100.0, 555.0]}]
    at = {"label": "decode_multi@H4B64", "lanes": 40, "ctx_tokens": 16000, "horizon": 4}
    ann = [
        ["loop.dispatch", 10.0, 20.0, {"label": "prefill_packed"}],
        ["loop.dispatch", 60.0, 440.0, at], ["runner.call", 70.0, 360.0, {"label": at["label"]}],
        ["runner.upload", 72.0, 10.0, {}], ["runner.enqueue", 84.0, 10.0, {}],
        ["runner.fetch", 95.0, 330.0, {}],
        ["loop.emit", 500.0, 20.0, {}],
        ["loop.dispatch", 520.0, 430.0, at], ["runner.call", 540.0, 390.0, {"label": at["label"]}],
        ["runner.upload", 541.0, 10.0, {}], ["runner.enqueue", 552.0, 8.0, {}],
        ["runner.fetch", 560.0, 368.0, {}],
        ["loop.yield", 950.0, 30.0, {}],
    ]
    return planes, ann


WANT = {"hop": 5.0, "upload": 2.0, "enqueue": 1.3, "fetch_lead": 0.5, "fetch_mid": 1.0,
        "fetch_drain": 5.3, "call_rest": 1.4}


def test_the_shares_read_what_was_built_in_and_add_up_to_the_dispatch_share():
    planes, ann = hand_made()
    out = ls.split(planes, ann)
    assert out["shares"] == pytest.approx(WANT)
    joined = hdj.join(planes, ann)
    assert joined["clock_ok"]
    assert out["sum"] == pytest.approx(joined["idle"]["dispatch"]) == pytest.approx(16.5)
    five = sum(out["shares"][p] for p in SHARES.values())
    assert five == pytest.approx(out["sum"] - WANT["fetch_mid"] - WANT["call_rest"])
    # per label, the means over its dispatches in ms: the same timeline with
    # a tenth of a millisecond for every nanosecond
    k = 1e5
    slow_planes = [{"span": [k * x for x in p["span"]], "starts": [],
                    "busy": [[k * x for x in b] for b in p["busy"]]} for p in planes]
    rows = ls.split(slow_planes, [[n, k * s, k * d, at] for n, s, d, at in ann])["by_label"]
    assert out["by_label"].keys() == rows.keys() == {"decode_multi@H4B64"}  # the third has no call
    assert rows["decode_multi@H4B64"] == {
        "dispatches": 2, "hop_ms": 1.5, "upload_ms": 1.0, "enqueue_ms": 0.9, "fetch_ms": 34.9,
        "fetch_device_ms": (290 + 340) / 20, "fetch_lead_ms": 0.25, "fetch_drain_ms": (25 + 28) / 20,
    }


def test_an_operation_over_a_phases_edge_is_cut_not_dropped():
    """B's only operation begins in its enqueue and ends in its fetch: the
    enqueue is idle up to it and no further, and the fetch has no lead."""
    planes, ann = hand_made()
    only_b = [a for a in ann if a[1] >= 500.0]
    shares = ls.split(planes, only_b)["shares"]
    assert shares["enqueue"] == pytest.approx(0.3)  # 552-555 of 552-560
    assert shares["fetch_lead"] == 0.0 and shares["fetch_mid"] == 0.0
    assert shares["fetch_drain"] == pytest.approx(2.8)  # 900-928
    device = ls._Busy(planes[0]["busy"])
    assert device.inside(560.0, 928.0) == [(560.0, 900.0)]
    assert device.inside(240.0, 270.0) == [(240.0, 250.0), (260.0, 270.0)]
    # a fetch in which the device ran nothing is all drain: its work ended
    assert ls.fetch_idle(device.inside(405.0, 425.0), 405.0, 425.0) == (0.0, 0.0, 20.0, 0.0)
    assert ls.intersect([(0.0, 10.0), (20.0, 30.0)], [(5.0, 25.0)]) == [(5.0, 10.0), (20.0, 25.0)]


def test_the_shares_are_the_mean_over_the_devices():
    planes, ann = hand_made()
    idle_one = {"span": [0.0, 1000.0], "busy": [], "starts": []}
    out = ls.split(planes + [idle_one], ann)
    # on the idle device every phase is idle for its whole length, a fetch
    # all drain; hop 50, upload 20, enqueue 18, the fetches 698, the rest 14
    assert out["shares"]["upload"] == pytest.approx((2.0 + 2.0) / 2)
    assert out["shares"]["enqueue"] == pytest.approx((1.3 + 1.8) / 2)
    assert out["shares"]["fetch_drain"] == pytest.approx((5.3 + 69.8) / 2)
    assert out["shares"]["fetch_lead"] == pytest.approx(0.5 / 2)
    assert out["sum"] == pytest.approx(hdj.join(planes + [idle_one], ann)["idle"]["dispatch"])


def test_nothing_to_split_gives_nothing():
    planes, ann = hand_made()
    assert ls.split([], ann) is None  # the CPU rehearsal: no device plane
    for gone in ls.CHILDREN.values():
        assert ls.split(planes, [a for a in ann if a[0] != gone]) is None
    assert ls.split(planes, []) is None


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(DATA, "join_slice.*.json.gz"))))
def test_the_parents_slices_give_nothing_and_raise_nothing(path):
    """Traces of the trees before this PR: `loop.dispatch` and `runner.call`
    are there, the three phases are not."""
    with gzip.open(path, "rt") as f:
        piece = json.load(f)
    assert hdj.join(piece["planes"], piece["annotations"]) is not None
    assert ls.split(piece["planes"], piece["annotations"]) is None


@pytest.mark.parametrize("cell", ["mistral7b-int8.chat-steady", "jamba2-3b-bf16.think-steady"])
def test_slice_of_the_chip_trace_reads_what_the_chip_run_read(cell):
    """Two seconds of this PR's traced chip runs (the devices' busy union,
    the `loop.*` and `runner.*` annotations) with what the chip run's own
    code read from them."""
    with gzip.open(os.path.join(DATA, f"launch_slice.{cell}.json.gz"), "rt") as f:
        piece = json.load(f)
    out = ls.split(piece["planes"], piece["annotations"])
    want = piece["read"]
    assert out["shares"] == pytest.approx(want["shares"], rel=1e-9, abs=1e-9)
    assert out["by_label"] == want["by_label"]
    joined = hdj.join(piece["planes"], piece["annotations"])
    assert joined["clock_share"] == piece["join"]["clock_share"] >= 0.95
    # the seven add up to the join's share, the five to within a point
    assert out["sum"] == pytest.approx(joined["idle"]["dispatch"], abs=1e-6)
    five = sum(out["shares"][p] for p in SHARES.values())
    assert 0.0 <= out["sum"] - five < 1.0
    assert out["sum"] > 5.0  # the launch is the largest part of the idle share
    row = next(v for k, v in out["by_label"].items() if k.startswith("decode_multi@"))
    assert row["dispatches"] >= 10
    # a dispatch's fetch holds the device's run; of the rest the upload is
    # the longest part (eleven arrays at half a millisecond each), not the hop
    assert row["fetch_device_ms"] > row["upload_ms"] + row["enqueue_ms"]
    assert row["upload_ms"] > row["enqueue_ms"] > 0 and row["upload_ms"] > 5 * row["hop_ms"] > 0
    assert out["shares"]["upload"] > out["sum"] / 2
    assert out["shares"]["fetch_lead"] < 0.1  # the device begins inside the enqueue
    assert row["fetch_ms"] >= row["fetch_device_ms"] + row["fetch_lead_ms"] + row["fetch_drain_ms"] - 1e-3


# ------------------------------------------------------------ the reader


def test_reader_reads_the_shares_once_made():
    planes, ann = hand_made()
    ctx = {"_launch_split": ls.split(planes, ann), "notes": {}}
    for name, part in SHARES.items():
        assert ls.read(ctx, params(name)) == pytest.approx(WANT[part])
    assert ctx["notes"] == {}
    assert ls.read({"_launch_split": None, "notes": {}}, params("idle_hop_share")) is None


def test_a_program_without_the_three_phases_has_its_trace_left_alone(monkeypatch, tmp_path):
    """The parent of this PR, run with these files laid over it: its phase
    table has `runner.call` and no `runner.upload`, its ledger no `launch`.
    The trace is not opened again; every metric of this reader gives nothing
    and nothing is noted."""
    def opened(*_a, **_k):
        raise AssertionError("the trace was opened")

    monkeypatch.setattr(ls, "load_annotations", opened)
    monkeypatch.setattr(ls, "profile_dir", lambda: str(tmp_path))
    planes, _ = hand_made()
    row = {"count": 10, "ms": 500.0, "self_ms": 500.0}
    ledger = {"phases": {"runner.call": row, "loop.dispatch": row}, "sampler": {"dispatches": 10}}
    ctx = {"trace": {"planes": planes}, "ledger0": ledger, "ledger1": ledger, "notes": {}}
    for name in NEW_METRICS:
        assert ls.read(ctx, params(name)) is None
    assert ctx["notes"] == {}
    # where `host_phases` would read 0 ms of a phase the program has not
    assert host_phases.read(ctx, {"kind": "ms_per_count", "phases": ["runner.upload"],
                                  "count_of": "runner.call"}) is None
    later = {**ledger, "phases": {k: {**v, "count": 2 * v["count"]} for k, v in ledger["phases"].items()}}
    assert host_phases.read({"ledger0": ledger, "ledger1": later}, {
        "kind": "ms_per_count", "phases": ["runner.upload"], "count_of": "runner.call"}) == 0.0
    assert ls.read({"ledger0": ledger, "ledger1": later}, params("launch_upload_ms")) is None


def test_a_trace_the_reader_cannot_read_costs_a_note_not_the_run(monkeypatch, tmp_path):
    def broken(*_a, **_k):
        raise OSError("no such trace")

    monkeypatch.setattr(ls, "load_annotations", broken)
    monkeypatch.setattr(ls.tr, "find_xplane", lambda where: where)
    monkeypatch.setattr(ls, "profile_dir", lambda: str(tmp_path))
    planes, _ = hand_made()
    row = {"count": 10, "ms": 5.0, "self_ms": 5.0}
    ledger = {"phases": {"runner.upload": row}}
    ctx = {"trace": {"planes": planes}, "ledger0": ledger, "ledger1": ledger, "notes": {}}
    assert ls.read(ctx, params("idle_hop_share")) is None
    assert ctx["notes"]["launch_split_error"] == "OSError: no such trace"
    assert ls.read(ctx, params("idle_upload_share")) is None  # and it is not tried again
    assert ls.read(ctx, {"kind": "no_such_kind"}) is None


def test_ledger_ratio_reads_the_windows_difference():
    a = {"launch": {"dispatches": 100, "upload_arrays": 1300, "upload_bytes": 1, "fetch_bytes": 1}}
    b = {"launch": {"dispatches": 700, "upload_arrays": 8200, "upload_bytes": 9, "fetch_bytes": 9}}
    ctx = {"ledger0": a, "ledger1": b, "notes": {}}
    assert ls.read(ctx, params("upload_arrays_per_dispatch")) == pytest.approx(6900 / 600)
    assert ls.read({"ledger0": b, "ledger1": b}, params("upload_arrays_per_dispatch")) is None
    assert ls.read({}, params("upload_arrays_per_dispatch")) is None


async def test_the_toy_engines_ledger_reads_as_the_rehearsal_reads_it():
    """What `--cpu-rehearsal --trace 1` has to read: the program's own table
    and ledger over a window give the two span metrics and the counter above
    0; the five shares, which need a device plane, are absent; nothing is
    noted as an error."""
    from dynamo_tpu.protocols.common import SamplingOptions
    from dynamo_tpu.telemetry import trace as dtrace
    from tests.test_jax_engine import collect
    from tests.test_layer_bodies import make_engine, request

    def ledger(engine):
        return {**engine.stats.goodput.summary(), "phases": dtrace.phase_summary()}

    engine = make_engine()
    greedy = SamplingOptions(greedy=True)
    try:
        await collect(engine, request([5, 6, 7, 8, 9], 8, greedy))
        opening = ledger(engine)
        await collect(engine, request(list(range(1, 20)), 16, greedy))
        close = ledger(engine)
    finally:
        await engine.close()
    ctx = {"ledger0": opening, "ledger1": close, "trace": {"planes": []}, "notes": {}}
    for name, phase in (("launch_upload_ms", "runner.upload"), ("launch_enqueue_ms", "runner.enqueue")):
        # what `host_phases` reads of the same table
        same = {"kind": "ms_per_count", "phases": [phase], "count_of": "runner.call"}
        got = ls.read(ctx, params(name))
        assert got > 0 and got == pytest.approx(host_phases.read(ctx, same)), name
    arrays = ls.read(ctx, params("upload_arrays_per_dispatch"))
    assert 11 <= arrays <= 32  # decode_multi's 11 up to a mixed step of two chunks
    for name in SHARES:
        assert ls.read(ctx, params(name)) is None
    assert "launch_split_error" not in ctx["notes"]


# ----------------------------------------------------------- the manifest


def test_new_metrics_resolve_and_are_declared_for_every_cell():
    bench = manifest.load_json("BENCHMARK.json")
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-len(NEW_METRICS):] == list(NEW_METRICS)
    for name in NEW_METRICS:
        m = manifest.load_json("cellbench", "metrics", name + ".json")
        entry = declared[name]
        assert "workloads" not in entry
        for key in ("unit", "better", "source", "layer", "moves"):
            assert m[key] == entry[key], (name, key)
        assert entry["layer"] == "ModelRunner step" and entry["moves"] == "tpot_p50_ms"
        reader = manifest.reader(m["reader"])
        kind = m["params"]["kind"]
        assert reader is ls and kind in ("idle_part", "ledger_ratio", "phase_ms_per_call")
        assert kind != "idle_part" or m["params"]["part"] in ls.PARTS
        assert kind != "phase_ms_per_call" or m["params"]["phase"] in ls.CHILDREN.values()
    for w in bench["workloads"]:
        names = {m["name"] for m in manifest.Cell(w["name"]).metrics("per_layer")}
        assert set(NEW_METRICS) <= names, w["name"]
