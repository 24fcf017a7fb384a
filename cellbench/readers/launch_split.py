"""The launch of a dispatch, split where the program splits it.

`host_device_join` reads `idle_dispatch_share`: the device's idle time inside
`loop.dispatch` up to the end of its `runner.call`. Since the runner records
three phases inside that call (`runner.upload`: every host array of the call
committed; `runner.enqueue`: the jitted call up to the return of its output
arrays; `runner.fetch`: the host read of the result), the same idle time has
seven places to lie in, in the order a dispatch passes them:

* `hop`: inside `loop.dispatch` before its `runner.call` begins (the event
  loop hands the call to the executor thread and goes on to run others);
* `upload`, `enqueue`: inside those two phases;
* `fetch_lead`: inside `runner.fetch` before the device's first operation
  in it (the runtime's own launch latency, inputs still travelling);
* `fetch_mid`: inside `runner.fetch` between two of the device's operations;
* `fetch_drain`: inside `runner.fetch` after the device's last operation in
  it (the result's copy, the thread's wake, the interpreter's lock). A fetch
  in which the device ran nothing is all drain: its work ended before;
* `call_rest`: inside `runner.call` and none of its three phases.

An operation that reaches over a phase's edge is cut there, not dropped: a
device that is busy when the fetch begins has no lead. The seven are shares
of the traced window in percent, mean over the devices, as the join's are,
and add up to its `dispatch` share. Five are metrics (`kind: idle_part`);
`fetch_mid`, `call_rest`, the sum, the join's own reading and, per label of
`loop.dispatch`, what one dispatch spends where in milliseconds go into the
window line's notes (`launch_split`).

The annotations are opened once for all of this reader's metrics, and not at
all for a program whose phase table has no `runner.upload` (the parent of the
PR that brought the three): it gives nothing, as does a trace without a
device plane (the CPU rehearsal) or one whose clocks do not join
(`host_device_join.MIN_CLOCK_SHARE`). Two seconds of what was read are kept
beside `join_slice.json.gz` for the tests (`piece_of`), the `loop.*` and
`runner.*` annotations alone.

Two kinds need no trace, only the ledger's readings at the window's opening
and close. `ledger_ratio`: the ledger's `slot[field]` over `slot[per]`
(`upload_arrays_per_dispatch`: `launch.upload_arrays` over
`launch.dispatches`). `phase_ms_per_call`: the phase table's ms of `phase`
over the count of `runner.call`, as `host_phases`' `ms_per_count` reads it,
but nothing for a program whose table has no such phase, where that reader
would say 0 ms (`launch_upload_ms`, `launch_enqueue_ms`).
"""

from __future__ import annotations

import gzip
import json
import os
import time
from bisect import bisect_left, bisect_right

from cellbench import trace_reduce as tr
from cellbench.readers.host_device_join import (
    cut, join, load_annotations, loop_states, overlap, plane_inputs, profile_dir, subtract,
)

CHILDREN = {"upload": "runner.upload", "enqueue": "runner.enqueue", "fetch": "runner.fetch"}
PARTS = ("hop", "upload", "enqueue", "fetch_lead", "fetch_mid", "fetch_drain", "call_rest")


def intersect(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """What two sorted, disjoint interval lists share."""
    return subtract(a, subtract(a, b))


def _spans(annotations: list[list], name: str) -> list[tuple[float, float]]:
    return sorted((s, s + d) for n, s, d, _ in annotations if n == name)


class _Busy:
    """One device's sorted, disjoint busy intervals, cut to a span."""

    def __init__(self, busy: list) -> None:
        self.busy = [tuple(b) for b in busy]
        self.starts = [b[0] for b in self.busy]
        self.ends = [b[1] for b in self.busy]

    def inside(self, lo: float, hi: float) -> list[tuple[float, float]]:
        near = self.busy[bisect_right(self.ends, lo):bisect_left(self.starts, hi)]
        return [(max(s, lo), min(e, hi)) for s, e in near]


def fetch_idle(inside: list[tuple[float, float]], lo: float, hi: float) -> tuple[float, float, float, float]:
    """(lead, mid, drain, busy) of one fetch [lo, hi) whose device was busy
    `inside` (cut to it)."""
    if not inside:
        return 0.0, 0.0, hi - lo, 0.0
    ran = sum(e - s for s, e in inside)
    lead, drain = inside[0][0] - lo, hi - inside[-1][1]
    return lead, (hi - lo) - ran - lead - drain, drain, ran


def split(planes: list[dict], annotations: list[list]) -> dict | None:
    """The seven shares, their sum, and the per-label milliseconds; None for
    a trace without a device plane or without the three phases."""
    names = {a[0] for a in annotations}
    if not planes or not {"loop.dispatch", "runner.call", *CHILDREN.values()} <= names:
        return None
    dispatch = loop_states(annotations)["dispatch"]
    calls = tr.merge(_spans(annotations, "runner.call"))
    in_call = intersect(dispatch, calls)
    where = {"hop": subtract(dispatch, calls)}
    for part, name in CHILDREN.items():
        where[part] = intersect(in_call, tr.merge(_spans(annotations, name)))
    where["call_rest"] = subtract(
        in_call, tr.merge([iv for part in CHILDREN for iv in where[part]])
    )
    window = sum(p["span"][1] - p["span"][0] for p in planes)
    ns = dict.fromkeys(PARTS, 0.0)
    devices = [_Busy(p["busy"]) for p in planes]
    for p, device in zip(planes, devices):
        idle = subtract([tuple(p["span"])], device.busy)
        for part in ("hop", "upload", "enqueue", "call_rest"):
            ns[part] += overlap(idle, where[part])
        for lo, hi in intersect(where["fetch"], [tuple(p["span"])]):
            lead, mid, drain, _ = fetch_idle(device.inside(lo, hi), lo, hi)
            ns["fetch_lead"] += lead
            ns["fetch_mid"] += mid
            ns["fetch_drain"] += drain
    shares = {part: 100.0 * v / window for part, v in ns.items()}
    return {
        "shares": shares, "sum": sum(shares.values()),
        "by_label": by_label(devices, annotations),
    }


BY_LABEL = ("hop", "upload", "enqueue", "fetch", "fetch_device", "fetch_lead", "fetch_drain")


def by_label(devices: list[_Busy], annotations: list[list]) -> dict:
    """Per label of `loop.dispatch`, the mean milliseconds one dispatch
    spends in the hop and in each phase of its call, and of its fetch the
    device's own time, the lead and the drain (mean over the devices)."""
    calls = _spans(annotations, "runner.call")
    call_starts = [c[0] for c in calls]
    kids = {part: _spans(annotations, name) for part, name in CHILDREN.items()}
    kid_starts = {part: [k[0] for k in spans] for part, spans in kids.items()}
    counts: dict[str, int] = {}
    total: dict[str, dict[str, float]] = {}
    for n, s, d, at in annotations:
        if n != "loop.dispatch":
            continue
        i = bisect_left(call_starts, s)
        if i >= len(calls) or calls[i][0] >= s + d:
            continue  # its call began outside the trace
        lo, hi = calls[i]
        label = str(at.get("label", ""))
        counts[label] = counts.get(label, 0) + 1
        row = total.setdefault(label, dict.fromkeys(BY_LABEL, 0.0))
        row["hop"] += lo - s
        for part, spans in kids.items():
            j = bisect_left(kid_starts[part], lo)
            while j < len(spans) and spans[j][0] < hi:
                row[part] += spans[j][1] - spans[j][0]
                if part == "fetch":
                    for device in devices:
                        lead, _, drain, ran = fetch_idle(device.inside(*spans[j]), *spans[j])
                        row["fetch_lead"] += lead / len(devices)
                        row["fetch_drain"] += drain / len(devices)
                        row["fetch_device"] += ran / len(devices)
                j += 1
    return {
        label: {"dispatches": counts[label], **{
            k + "_ms": round(v / 1e6 / counts[label], 4) for k, v in row.items()
        }}
        for label, row in sorted(total.items())
    }


# ------------------------------------------------------------ the reader


def _split_run(ctx: dict, program: str) -> dict | None:
    """The split of this run, made once and shared by this reader's metrics."""
    if "_launch_split" in ctx:
        return ctx["_launch_split"]
    ctx["_launch_split"] = None
    red = ctx.get("trace")
    where = profile_dir()
    phases = (ctx.get("ledger1") or {}).get("phases") or {}
    if not (CHILDREN["upload"] in phases and red and red["planes"] and where and os.path.isdir(where)):
        return None
    t0 = time.monotonic()
    annotations = [
        a for a in load_annotations(tr.find_xplane(where))
        if a[0].startswith(("loop.", "runner."))
    ]
    planes = plane_inputs(red, program)
    joined = join(planes, annotations)
    out = split(planes, annotations)
    if joined is None or out is None or not joined["clock_ok"]:
        return None
    shares = out["shares"]
    ctx.setdefault("notes", {})["launch_split"] = {
        "idle_fetch_mid_share": shares["fetch_mid"], "idle_call_rest_share": shares["call_rest"],
        "sum": out["sum"], "idle_dispatch_share": joined["idle"]["dispatch"],
        "clock_share": joined["clock_share"], "by_label": out["by_label"],
        # what this reader costs the traced run: the file is opened again
        "reader_seconds": time.monotonic() - t0,
    }
    lo = planes[0]["span"][0] + 1.0e9
    with gzip.open(os.path.join(os.path.dirname(where), "launch_slice.json.gz"), "wt") as f:
        json.dump(piece_of(planes, annotations, lo, lo + 2.0e9), f)
    ctx["_launch_split"] = out
    return out


def piece_of(planes: list[dict], annotations: list[list], lo: float, hi: float,
             within_ns: float = 8.0) -> dict:
    """What lies inside [lo, hi) with what this code reads from it, small
    enough to keep beside the tests: the device's operations that follow one
    another within `within_ns` are kept as one busy interval (a step program
    is some hundred thousand operations a second, 1 or 2 ns apart: all but
    a thousandth of the intervals, a thousandth of the idle time)."""
    piece = cut(planes, annotations, lo, hi)
    for p in piece["planes"]:
        joined: list[list[float]] = []
        for s, e in p["busy"]:
            if joined and s - joined[-1][1] <= within_ns:
                joined[-1][1] = e
            else:
                joined.append([s, e])
        p["busy"] = joined
    piece["read"] = split(piece["planes"], piece["annotations"])
    piece["join"] = join(piece["planes"], piece["annotations"])
    return piece


def _ledger_ratio(ctx: dict, params: dict):
    a, b = ctx.get("ledger0") or {}, ctx.get("ledger1") or {}
    s0, s1 = a.get(params["slot"]), b.get(params["slot"])
    if not isinstance(s0, dict) or not isinstance(s1, dict):
        return None
    per = float(s1.get(params["per"], 0)) - float(s0.get(params["per"], 0))
    if per <= 0:
        return None
    return (float(s1.get(params["field"], 0)) - float(s0.get(params["field"], 0))) / per


def _phase_ms_per_call(ctx: dict, params: dict):
    p0 = (ctx.get("ledger0") or {}).get("phases")
    p1 = (ctx.get("ledger1") or {}).get("phases")
    if p0 is None or p1 is None or params["phase"] not in p1:
        return None
    calls = p1.get("runner.call", {}).get("count", 0) - p0.get("runner.call", {}).get("count", 0)
    if calls <= 0:
        return None
    return (p1[params["phase"]]["ms"] - p0.get(params["phase"], {}).get("ms", 0.0)) / calls


def read(ctx: dict, params: dict):
    try:
        if params["kind"] == "ledger_ratio":
            return _ledger_ratio(ctx, params)
        if params["kind"] == "phase_ms_per_call":
            return _phase_ms_per_call(ctx, params)
        if params["kind"] != "idle_part":
            raise ValueError(f"unknown kind {params['kind']!r}")
        out = _split_run(ctx, params.get("program", "decode_multi"))
        return None if out is None else out["shares"][params["part"]]
    except Exception as e:  # noqa: BLE001: a metric gives nothing, it costs no run
        ctx.setdefault("notes", {})["launch_split_error"] = f"{type(e).__name__}: {e}"
        return None
