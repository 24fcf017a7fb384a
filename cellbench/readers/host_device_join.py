"""Metrics that join the program's own phases to the device's timeline.

While a profile window is open every process-level phase of the program
(`dynamo_tpu/telemetry/trace.py::phase`) is a profiler annotation
`dyn:<name>` in the `.xplane.pb`, on the clock of the device's operations.
`cellbench/trace_reduce.py::load_xplane` keeps only Python frames, so this
reader opens the file again for the annotations; it finds it itself, under
`cellbench_out/<--workload>/profile` where `run.py` (the `__main__`) had the
server write it. A program whose ledger has no `phases` (the parent of the
PR that brought them) wrote no annotation: its trace is left alone and gives
nothing, as does a trace without a device plane (the CPU rehearsal).

The device's idle time inside the traced window is split by where the
engine loop's task was:

* `dispatch`: inside `loop.dispatch` up to the end of its `runner.call`
  (hop to the executor, upload, launch, fetch);
* `frontend`: inside `loop.dispatch` after its `runner.call` has ended (the
  result is there and the engine's task waits for the event loop), or in
  `loop.yield` or `loop.idle`: the event loop is serving others;
* `packer`: in `loop.reap`, `.admit`, `.pack`, `.emit` or `.stats`;
* the remainder, under none of them, which goes into the notes.

The four add up to `device_idle_share`. That the two clocks are one is
checked, not assumed: the share of the runner's calls of `program` whose
execution (the longest that `trace_reduce` gives the call) begins inside a
`runner.call` annotation is noted, and under `MIN_CLOCK_SHARE` every metric
of this reader is withheld.

`kind`: `idle_share` (with `part`) or `attn_roofline`.
"""

from __future__ import annotations

import gzip
import importlib
import json
import os
import sys
from bisect import bisect_left, bisect_right

from cellbench import trace_reduce as tr
from cellbench.manifest import hf_config, load_json
from cellbench.peaks import peaks_for

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PREFIX = "dyn:"
PACKER = ("loop.reap", "loop.admit", "loop.pack", "loop.emit", "loop.stats")
WAITING = ("loop.yield", "loop.idle")
MIN_CLOCK_SHARE = 0.95


def profile_dir() -> str | None:
    """`cellbench_out/<--workload>/profile` of the run that is `__main__`."""
    argv = sys.argv
    for i, arg in enumerate(argv):
        if arg == "--workload" and i + 1 < len(argv):
            return os.path.join(ROOT, "cellbench_out", argv[i + 1], "profile")
        if arg.startswith("--workload="):
            return os.path.join(ROOT, "cellbench_out", arg.split("=", 1)[1], "profile")
    return None


def load_annotations(path: str, lay: dict | None = None) -> list[list]:
    """[name, start_ns, duration_ns, attributes] of every `dyn:` event on a
    host plane, the prefix taken off."""
    from jax.profiler import ProfileData

    lay = lay or tr.layout()
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(lay["host_prefix"]):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith(PREFIX):
                    out.append([
                        name[len(PREFIX):], float(ev.start_ns), float(ev.duration_ns),
                        {str(k): v for k, v in ev.stats},
                    ])
    return out


# ------------------------------------------------------------- arithmetic


def overlap(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """a minus b, both sorted and disjoint."""
    out = []
    j = 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > lo:
                out.append((lo, b[k][0]))
            lo = max(lo, b[k][1])
            k += 1
        if lo < hi:
            out.append((lo, hi))
    return out


def _spans(annotations: list[list], names) -> list[tuple[float, float]]:
    return [(s, s + d) for n, s, d, _ in annotations if n in names]


def loop_states(annotations: list[list]) -> dict:
    """Where the engine loop's task was, as three disjoint interval lists."""
    calls = sorted(_spans(annotations, ("runner.call",)))
    call_starts = [c[0] for c in calls]
    pre, post = [], []
    for s, e in _spans(annotations, ("loop.dispatch",)):
        i = bisect_left(call_starts, s)
        cut = min(calls[i][1], e) if i < len(calls) and calls[i][0] < e else e
        pre.append((s, cut))
        if cut < e:
            post.append((cut, e))
    dispatch = tr.merge(pre)
    frontend = subtract(tr.merge(post + _spans(annotations, WAITING)), dispatch)
    packer = subtract(tr.merge(_spans(annotations, PACKER)), tr.merge(dispatch + frontend))
    return {"dispatch": dispatch, "frontend": frontend, "packer": packer}


def join(planes: list[dict], annotations: list[list],
         min_clock_share: float = MIN_CLOCK_SHARE) -> dict | None:
    """`planes`: per device `{"span": (lo, hi), "busy": [(s, e), ...],
    "starts": [start of each execution of the checked program]}`. Gives the
    idle shares (% of the traced window, mean over the devices), the clock
    check, and the mean live lanes and context of the program's dispatches;
    None where there is nothing to join."""
    if not planes or not {"runner.call", "loop.dispatch"} <= {a[0] for a in annotations}:
        return None
    states = loop_states(annotations)
    window = sum(p["span"][1] - p["span"][0] for p in planes)
    idle_ns = {k: 0.0 for k in (*states, "all")}
    for p in planes:
        idle = subtract([tuple(p["span"])], [tuple(b) for b in p["busy"]])
        idle_ns["all"] += tr.total(idle)
        for k, spans in states.items():
            idle_ns[k] += overlap(idle, spans)
    shares = {k: 100.0 * v / window for k, v in idle_ns.items()}
    shares["unattributed"] = shares["all"] - sum(shares[k] for k in states)
    calls = tr.merge(_spans(annotations, ("runner.call",)))
    call_starts = [c[0] for c in calls]
    # only while the program was annotating: the profiler's tracers run a
    # little longer than the program's switch, and a dispatch launched in
    # between is in the trace without its annotations
    edges = _spans(annotations, ("loop.dispatch",))
    first, last = min(s for s, _ in edges), max(e for _, e in edges)
    starts = [s for p in planes for s in p["starts"] if first <= s <= last]
    inside = 0
    for s in starts:
        i = bisect_right(call_starts, s) - 1
        inside += bool(i >= 0 and s < calls[i][1])
    clock = inside / len(starts) if starts else 0.0
    return {
        "idle": shares, "clock_share": clock, "executions_checked": len(starts),
        "clock_ok": bool(starts) and clock >= min_clock_share,
        "annotations": len(annotations),
    }


def live_lanes_context(annotations: list[list], label_prefix: str) -> dict | None:
    """Mean live lanes, and the mean context a lane held over the steps of a
    dispatch, from the `loop.dispatch` annotations whose label starts with
    `label_prefix`: the counter inside the program that the client-side
    guess (`clientmath.live_lanes_context`) stood in for."""
    rows = [
        a[3] for a in annotations
        if a[0] == "loop.dispatch" and str(a[3].get("label", "")).startswith(label_prefix)
        and float(a[3].get("lanes", 0)) > 0
    ]
    if not rows:
        return None
    lanes = sum(float(r["lanes"]) for r in rows)
    # a lane's context grows by one a step: mean over the horizon's steps
    ctx_tokens = sum(
        float(r["ctx_tokens"]) + float(r["lanes"]) * (float(r.get("horizon", 1)) - 1) / 2
        for r in rows
    )
    return {"dispatches": len(rows), "lanes": lanes / len(rows), "context": ctx_tokens / lanes}


# ------------------------------------------------------------ the reader


def plane_inputs(red: dict, program: str) -> list[dict]:
    """What `join` needs of the reduced trace. The checked executions are
    one per call of the runner's `program`: the longest, the program itself.
    `trace_reduce` gives a call every execution up to the runner's next
    call, and a small one launched from the event loop in between (a copy
    of finished blocks to the host tier) begins inside no `runner.call`."""
    out = []
    for p in red["planes"]:
        longest: dict[int, list] = {}
        for m in tr.modules_named(p, program)[0]:
            if m[3] not in longest or m[2] > longest[m[3]][2]:
                longest[m[3]] = m
        out.append({
            "span": list(p["span"]), "busy": [list(b) for b in p["busy"]],
            "starts": sorted(m[1] for m in longest.values()),
        })
    return out


def cut(planes: list[dict], annotations: list[list], lo: float, hi: float) -> dict:
    """What lies inside [lo, hi): small enough to keep beside the tests."""
    return {
        "planes": [
            {"span": [max(lo, p["span"][0]), min(hi, p["span"][1])],
             "busy": [[max(s, lo), min(e, hi)] for s, e in p["busy"] if e > lo and s < hi],
             "starts": [s for s in p["starts"] if lo <= s < hi]}
            for p in planes
        ],
        # an annotation that reaches over an edge is cut there, not dropped:
        # a dispatch is a third of the piece
        "annotations": [
            [n, max(s, lo), min(s + d, hi) - max(s, lo), at]
            for n, s, d, at in annotations if s + d > lo and s < hi
        ],
    }


def _joined(ctx: dict, program: str) -> dict | None:
    """The join of this run, made once and shared by this reader's metrics."""
    if "_host_device_join" in ctx:
        return ctx["_host_device_join"]
    ctx["_host_device_join"] = out = None
    red = ctx.get("trace")
    where = profile_dir()
    # a program without the phase table wrote no `dyn:` annotation either:
    # its trace is not opened a second time
    annotating = (ctx.get("ledger1") or {}).get("phases") is not None
    if annotating and red and red["planes"] and where and os.path.isdir(where):
        annotations = load_annotations(tr.find_xplane(where))
        planes = plane_inputs(red, program)
        out = join(planes, annotations)
        if out is not None:
            out["live"] = live_lanes_context(annotations, program)
            ctx.setdefault("notes", {})["host_device_join"] = {
                "idle_unattributed_share": out["idle"]["unattributed"],
                "idle_share": out["idle"]["all"],
                "clock_share": out["clock_share"],
                "executions_checked": out["executions_checked"],
                "annotations": out["annotations"], "live": out["live"],
            }
            # two seconds of it, with what this code reads from it, for
            # the tests
            lo = planes[0]["span"][0] + 1.0e9
            piece = cut(planes, annotations, lo, lo + 2.0e9)
            piece["read"] = join(piece["planes"], piece["annotations"])
            piece["live"] = live_lanes_context(piece["annotations"], program)
            with gzip.open(os.path.join(os.path.dirname(where), "join_slice.json.gz"), "wt") as f:
                json.dump(piece, f)
            ctx["_host_device_join"] = out
    return out


def read(ctx: dict, params: dict):
    try:
        out = _joined(ctx, params.get("program", "decode_multi"))
    except Exception as e:  # noqa: BLE001 — a trace this reader cannot read
        # gives nothing, with the reason in the window line's notes; it
        # must not cost the run its other metrics
        ctx.setdefault("notes", {})["host_device_join_error"] = f"{type(e).__name__}: {e}"
        out = None
    if out is None or not out["clock_ok"]:
        return None
    kind = params["kind"]
    if kind == "idle_share":
        return out["idle"][params["part"]]
    if kind == "attn_roofline":
        live = out["live"]
        if not live:
            return None
        from cellbench.readers import device_trace

        kernel = load_json("cellbench", "metrics", params["kernel_metric"] + ".json")
        kernel_ms = device_trace.read(ctx, kernel["params"])
        if not kernel_ms:
            return None
        bench = ctx["config"]["bench"]
        ref = importlib.import_module(f"cellbench.reference.{bench['reference']}")
        counts = importlib.import_module(f"cellbench.counts.{bench['counts']}")
        c = counts.step_counts(ref.dims(hf_config(ctx["config"])), live["lanes"], live["context"])
        least_ms = 1e3 * c["kv_bytes"] / peaks_for(ctx["facts"]["device_kind"])["hbm_bytes_per_s"]
        ctx.setdefault("notes", {})[params.get("note", "attn_kernel_roofline")] = {
            "kv_bytes": c["kv_bytes"], "least_ms": least_ms, "kernel_ms": kernel_ms,
            "lanes": live["lanes"], "context": live["context"],
        }
        return 100.0 * least_ms / kernel_ms
    raise ValueError(f"unknown kind {kind!r}")
