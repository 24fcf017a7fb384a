"""Metrics from the device trace of a `--trace 1` run (reduced by
`cellbench/trace_reduce.py`). A run without a trace gives nothing."""

from __future__ import annotations

import importlib

from cellbench import trace_reduce as tr
from cellbench.manifest import hf_config
from cellbench.peaks import peaks_for


def _dispatches(ctx: dict, params: dict):
    """(operations inside the executions of the runner's `program`, steps
    they made) over all device planes; steps = executions x the decode
    horizon."""
    red = ctx["trace"]
    ops, runs = [], 0
    for p in red["planes"]:
        mods, calls = tr.modules_named(p, params["program"])
        runs += calls
        ops += tr.in_modules(p["ops"], mods)
    steps = runs * int(ctx["facts"]["decode_horizon"]) / max(1, len(red["planes"]))
    return ops, steps


def _module_busy_ms_per_step(ctx: dict, params: dict):
    ops, steps = _dispatches(ctx, params)
    if not steps:
        return None
    busy = tr.total(tr.merge([(e[1], e[1] + e[2]) for e in ops]))
    return busy / 1e6 / len(ctx["trace"]["planes"]) / steps


def read(ctx: dict, params: dict):
    red = ctx.get("trace")
    if not red or not red["planes"]:
        return None
    kind = params["kind"]
    if kind == "idle_share":
        return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    if kind == "class_share":
        ops = [e for p in red["planes"] for e in p["ops"]]
        whole = sum(e[2] for e in ops) / 1e9
        return 100.0 * tr.class_seconds(ops, params["classes"]) / whole
    if kind == "module_busy_ms_per_step":
        return _module_busy_ms_per_step(ctx, params)
    if kind == "class_ms_per_step":
        ops, steps = _dispatches(ctx, params)
        if not steps:
            return None
        n = len(red["planes"])
        return 1e3 * tr.class_seconds(ops, params["classes"]) / n / steps
    if kind == "roofline":
        device_ms = _module_busy_ms_per_step(ctx, params)
        live = ctx["client"].get("live")
        if not device_ms or not live:
            return None
        bench = ctx["config"]["bench"]
        ref = importlib.import_module(f"cellbench.reference.{bench['reference']}")
        counts = importlib.import_module(f"cellbench.counts.{bench['counts']}")
        d = ref.dims(hf_config(ctx["config"]))
        c = counts.step_counts(d, live["lanes"], live["context"])
        least, bound = counts.least_seconds(c, peaks_for(ctx["facts"]["device_kind"]))
        ctx.setdefault("notes", {})[params.get("note", "roofline")] = {
            "bound": bound, "least_ms": least * 1e3, "device_ms": device_ms,
            "lanes": live["lanes"], "context": live["context"],
        }
        return 100.0 * least * 1e3 / device_ms
    raise ValueError(f"unknown kind {kind!r}")
