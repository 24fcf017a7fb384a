"""From a profiler trace to numbers: busy union, idle share, operation
classes, per-dispatch grouping, longest idle gaps by what the host was doing.

A trace here is a plain structure, so the arithmetic can be checked on a
small recorded one without the profiler:

    {"planes": [{"name": str, "lines": [{"name": str,
        "events": [[name, start_ns, duration_ns], ...]}]}]}

`load_xplane` makes it from the `.xplane.pb` that `jax.profiler` writes
(`jax.profiler.ProfileData`; nothing else is needed). Device planes are those
whose name starts with `device_prefix`; on such a plane the line `ops_line`
holds one event per device operation and `modules_line` one per executed
program. Host planes hold the Python tracer's frames (`$file:line function`).
All of those names are parameters, kept in `cellbench/trace_layout.json`.
"""

from __future__ import annotations

import glob
import json
import os
import re
from bisect import bisect_right

HERE = os.path.dirname(os.path.abspath(__file__))


def layout() -> dict:
    with open(os.path.join(HERE, "trace_layout.json")) as f:
        return json.load(f)


def find_xplane(profile_dir: str) -> str:
    found = sorted(
        glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True)
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return found[-1]


def load_xplane(path: str, lay: dict | None = None) -> dict:
    """The neutral structure from an .xplane.pb. Host lines are kept only
    where they hold Python frames, and only the frames that overlap the
    device's own span: a server's trace has millions of others."""
    from jax.profiler import ProfileData

    lay = lay or layout()
    data = ProfileData.from_file(path)
    planes = []
    lo, hi = None, None
    raw = list(data.planes)
    for plane in raw:
        if not plane.name.startswith(lay["device_prefix"]):
            continue
        lines = []
        for line in plane.lines:
            events = [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ev in line.events
            ]
            lines.append({"name": line.name, "events": events})
            for _, s, d in events:
                lo = s if lo is None else min(lo, s)
                hi = s + d if hi is None else max(hi, s + d)
        planes.append({"name": plane.name, "lines": lines})
    for plane in raw:
        if not plane.name.startswith(lay["host_prefix"]):
            continue
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                name = ev.name
                if not name.startswith(lay["python_frame_prefix"]):
                    continue
                s, d = float(ev.start_ns), float(ev.duration_ns)
                if lo is not None and (s + d < lo or s > hi):
                    continue
                events.append([name, s, d])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# ------------------------------------------------------------- arithmetic


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: list[tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def base_name(name: str) -> str:
    """`%copy.12 = ...` and `copy.12` both give `copy`."""
    name = name.strip().lstrip("%").split(" ", 1)[0]
    return re.sub(r"[.\d]+$", "", name)


def device_planes(trace: dict, lay: dict) -> list[dict]:
    return [p for p in trace["planes"] if p["name"].startswith(lay["device_prefix"])]


def line_events(plane: dict, line_name: str) -> list[list]:
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def plane_span(plane: dict) -> tuple[float, float] | None:
    """First start to last end over every line of a device plane: the
    traced window as that device saw it."""
    starts = [e[1] for l in plane["lines"] for e in l["events"]]
    ends = [e[1] + e[2] for l in plane["lines"] for e in l["events"]]
    return (min(starts), max(ends)) if starts else None


def in_modules(ops: list[list], modules: list[list]) -> list[list]:
    """The operations that start inside one of the module executions."""
    spans = merge([(m[1], m[1] + m[2]) for m in modules])
    starts = [s for s, _ in spans]
    out = []
    for ev in ops:
        i = bisect_right(starts, ev[1]) - 1
        if i >= 0 and ev[1] < spans[i][1]:
            out.append(ev)
    return out


def program_calls(trace: dict, lay: dict) -> list[tuple[float, str]]:
    """(start, function) of every host frame that launches one of the
    runner's programs, in time order. The device's own name for a program
    (`jit__unknown(<hash>)`) says nothing, so an execution is given to the
    runner method that was called last before it started: dispatches are
    serialised (one in flight, fetched before the next), so that is the one
    that launched it."""
    want = set(lay["program_functions"])
    prefix = lay["python_frame_prefix"] + lay["program_file"] + ":"
    calls = []
    for plane in trace["planes"]:
        if not plane["name"].startswith(lay["host_prefix"]):
            continue
        for line in plane["lines"]:
            for name, start, _ in line["events"]:
                if name.startswith(prefix) and name.rsplit(" ", 1)[-1] in want:
                    calls.append((start, name.rsplit(" ", 1)[-1]))
    return sorted(calls)


def reduce_device(trace: dict, lay: dict | None = None) -> dict:
    """Per device plane: window, busy union, every operation event and
    every module execution with the runner method that launched it.
    `busy_s` and `window_s` are means over the device planes, as the result
    line wants them."""
    lay = lay or layout()
    calls = program_calls(trace, lay)
    call_starts = [c[0] for c in calls]
    planes = []
    for plane in device_planes(trace, lay):
        ops = [e for e in line_events(plane, lay["ops_line"]) if e[2] > 0]
        span = plane_span(plane)
        if not ops or span is None:
            continue
        busy = merge([(e[1], e[1] + e[2]) for e in ops])
        # [program, start, duration, call]: `call` numbers the runner call
        # that launched it (-1: it began before the trace's first call)
        modules = []
        for name, start, dur in line_events(plane, lay["modules_line"]):
            i = bisect_right(call_starts, start) - 1
            modules.append([calls[i][1] if i >= 0 else name, start, dur, i])
        planes.append({
            "name": plane["name"], "span": span, "busy": busy, "ops": ops,
            "modules": modules,
        })
    if not planes:
        return {"planes": [], "busy_s": 0.0, "window_s": 0.0}
    n = len(planes)
    return {
        "planes": planes,
        "busy_s": sum(total(p["busy"]) for p in planes) / n / 1e9,
        "window_s": sum(p["span"][1] - p["span"][0] for p in planes) / n / 1e9,
    }


def class_seconds(ops: list[list], patterns: list[str]) -> float:
    """Summed duration of the operations whose base name matches any of the
    regular expressions."""
    rx = [re.compile(p) for p in patterns]
    return sum(
        e[2] for e in ops if any(r.search(base_name(e[0])) for r in rx)
    ) / 1e9


def modules_named(plane: dict, program: str) -> tuple[list[list], int]:
    """The executions launched by calls of the runner method `program`, and
    the number of those calls. A call may launch small programs beside the
    one it is named for; they are its work too. Executions that touch the
    trace's edges are cut short and are left out."""
    lo, hi = plane["span"]
    mine = [
        m for m in plane["modules"]
        if m[0] == program and m[3] >= 0 and m[1] > lo and m[1] + m[2] < hi
    ]
    return mine, len({m[3] for m in mine})


def slice_trace(trace: dict, lo: float, hi: float, max_name: int = 140) -> dict:
    """The events that start in [lo, hi), cut off at hi, names cut to
    `max_name`: a trace small enough to keep beside the tests."""
    planes = []
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            events = [
                [n[:max_name], s, min(d, hi - s)] for n, s, d in line["events"] if lo <= s < hi
            ]
            if events:
                lines.append({"name": line["name"], "events": events})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


def top_ops(reduced: dict, k: int = 10) -> list[list]:
    """The k operation names with the most device time, over all planes."""
    acc: dict[str, float] = {}
    for p in reduced["planes"]:
        for name, _, dur in p["ops"]:
            key = op_label(name)
            acc[key] = acc.get(key, 0.0) + dur / 1e9
    return [[n, s] for n, s in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def op_label(name: str) -> str:
    """A stable, short label: the base name and, where the event's name
    carries its result shape (`%copy.1 = bf16[1,8,3400,16,128]{...} copy(`),
    the shape; letters, digits, `_`, `-` only."""
    label = base_name(name)
    m = re.search(r"=\s*\(?([a-z0-9]+)\[([\d,]*)\]", name)
    if m:
        label += "_" + m.group(1) + "_" + m.group(2).replace(",", "_")
    return re.sub(r"[^A-Za-z0-9_\-]", "_", label)[:80]


def idle_gaps(reduced: dict, trace: dict, lay: dict | None = None,
              k: int = 10, longest: int = 200) -> list[list]:
    """The device's idle time inside its window, by what the host was
    doing: each of the `longest` longest gaps of the first device plane is
    given to the innermost Python frame that covers its middle, and the
    gaps' seconds are summed by frame. The k largest sums."""
    lay = lay or layout()
    if not reduced["planes"]:
        return []
    p = reduced["planes"][0]
    edges = [p["span"][0]] + [x for iv in p["busy"] for x in iv] + [p["span"][1]]
    gaps = sorted(
        ((edges[i + 1] - edges[i], edges[i]) for i in range(0, len(edges), 2)),
        reverse=True,
    )[:longest]
    import numpy as np

    frames = [
        e for plane in trace["planes"]
        if plane["name"].startswith(lay["host_prefix"])
        for line in plane["lines"] for e in line["events"]
    ]
    starts = np.array([e[1] for e in frames], dtype=np.float64)
    durs = np.array([e[2] for e in frames], dtype=np.float64)
    acc: dict[str, float] = {}
    for dur, start in gaps:
        if dur <= 0:
            continue
        mid = start + dur / 2
        key = "no_python_frame"
        if len(frames):
            cover = np.flatnonzero((starts <= mid) & (mid < starts + durs))
            if len(cover):
                inner = cover[np.argmin(durs[cover])]
                key = re.sub(r"[^A-Za-z0-9_.\-]", "_", frames[inner][0])
        acc[key] = acc.get(key, 0.0) + dur / 1e9
    return [[n, s] for n, s in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def save(trace: dict, path: str) -> None:
    import gzip

    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def load(path: str) -> dict:
    import gzip

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def describe_xplane(path: str, per_line: int = 4) -> str:
    """Planes, lines, event counts and a few events with their stats: look
    at a trace by hand before writing code against it."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines[:40]:
            events = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(events)}")
            for ev in events[:per_line]:
                stats = [(k, str(v)[:120]) for k, v in list(ev.stats)[:8]]
                out.append(
                    f"    {ev.name[:160]!r} start={ev.start_ns} dur={ev.duration_ns} {stats}"
                )
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(describe_xplane(find_xplane(sys.argv[1])))
