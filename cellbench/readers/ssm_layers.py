"""Metrics of a program with recurrent (state-space) layers: the time its
state update and its prefill scans take in the device trace, the update's
share of its roofline, and the live state slots from the engine's ledger
(`ssm` in `GET /debug/goodput`: counted on the host where the lane arrays
are built).

The trace's operation records carry the HLO instruction and no
`jax.named_scope`, so an operation is found by the arrays it touches: a
pattern, given in the metric's file, searched in the instruction (its result
type and as much of its operands as the record keeps). `{d_state}`,
`{d_inner}` and the other sizes of the reference's `dims` may stand in a
pattern. `ssm_step_ms` thus finds every operation that writes or reads a
`[lanes, d_state, d_inner]` float32 state, whatever implements or names it.
Today, of the four steps of a dispatch, three of a layer's are one fusion that
reads the dispatch's first state and recomputes the steps up to its own on
the way to the product with C (no state is stored), and the fourth is one
fusion that also writes the new state over all rows under the mask; beside
them the waits on the compiler's asynchronous copies of the slots' arrays
(`copy-done`, `async-done`: the exposed part of a transfer). So the share of
the roofline counts the state read in every step and written once a dispatch
(`counts.scan_state_step_bytes` at the server's `decode_horizon`), the state's
bytes alone and not the tail's. A `while` is the wrapper of a device loop and
holds its body's operations' time a second time: it is left out.

A program without the counters (the parent of the PR that brought them), a
run without a trace, or a trace in which the pattern finds nothing gives
nothing: the reader returns None and never raises.

`kind`: `result_ms_per_step`, `state_roofline`, `slots_live`,
`result_ms_per_ktok`.
"""

from __future__ import annotations

import re

from cellbench import trace_reduce as tr
from cellbench.manifest import load_json
from cellbench.peaks import peaks_for
from cellbench.readers.device_trace import _dispatches
from cellbench.readers.expert_layers import _model


def _ssm_delta(ctx: dict) -> dict | None:
    a, b = ctx.get("ledger0") or {}, ctx.get("ledger1") or {}
    if not isinstance(a.get("ssm"), dict) or not isinstance(b.get("ssm"), dict):
        return None
    delta = {k: float(b["ssm"].get(k, 0.0)) - float(a["ssm"].get(k, 0.0)) for k in b["ssm"]}
    return delta if delta.get("layer_steps", 0.0) > 0 else None


def _matching_ns(ops: list, rx) -> float:
    return sum(
        e[2] for e in ops if " while(" not in e[0] and rx.search(e[0])
    )


def _result_ms_per_step(ctx: dict, params: dict):
    red = ctx.get("trace")
    if not red or not red.get("planes"):
        return None
    d, _ = _model(ctx)
    ops, steps = _dispatches(ctx, params)
    if not steps:
        return None
    ns = _matching_ns(ops, re.compile(params["pattern"].format(**d)))
    if ns <= 0:
        return None
    return ns / 1e6 / len(red["planes"]) / steps


def _result_ms_per_ktok(ctx: dict, params: dict):
    red = ctx.get("trace")
    ssm = _ssm_delta(ctx)
    if not red or not red.get("planes") or ssm is None:
        return None
    d, _ = _model(ctx)
    rx = re.compile(params["pattern"].format(**d))
    ns = 0.0
    for p in red["planes"]:
        for program in params["programs"]:
            mods, _ = tr.modules_named(p, program)
            ns += _matching_ns(tr.in_modules(p["ops"], mods), rx)
    tokens = _scanned_in_trace(ctx, red, params)
    if tokens is None:
        # no annotation to count from: the window's prompt tokens, by the
        # share of the window that was traced
        tokens = ssm["scan_tokens"] * red["window_s"] / float(params["window_seconds"])
    if ns <= 0 or tokens <= 0:
        return None
    return 1000.0 * (ns / 1e6 / len(red["planes"])) / tokens


def _scanned_in_trace(ctx: dict, red: dict, params: dict):
    """Prompt tokens of the dispatches whose programs ran inside the traced
    stretch: the `prefill_tokens` of their `loop.dispatch` annotations
    (`ctx["annotations"]` where a test hands them over, else the run's own
    profile)."""
    annotations = ctx.get("annotations")
    if annotations is None:
        from cellbench.readers import host_device_join as hdj

        where = hdj.profile_dir()
        if not where:
            return None
        try:
            annotations = hdj.load_annotations(tr.find_xplane(where))
        except Exception:  # noqa: BLE001: no profile to read
            return None
    lo = min(p["span"][0] for p in red["planes"])
    hi = max(p["span"][1] for p in red["planes"])
    labels = tuple(params["labels"])
    found = [
        float(at.get("prefill_tokens", 0) or 0)
        for name, start, dur, at in annotations
        if name == "loop.dispatch" and str(at.get("label", "")).startswith(labels)
        and start > lo and start + dur < hi
    ]
    return sum(found) if found else None


def read(ctx: dict, params: dict):
    try:
        return _read(ctx, params)
    except Exception as e:  # noqa: BLE001: a metric gives nothing, it costs no run
        ctx.setdefault("notes", {})["ssm_layers_error"] = f"{type(e).__name__}: {e}"
        return None


def _read(ctx: dict, params: dict):
    kind = params["kind"]
    if kind == "result_ms_per_step":
        return _result_ms_per_step(ctx, params)
    if kind == "result_ms_per_ktok":
        return _result_ms_per_ktok(ctx, params)
    ssm = _ssm_delta(ctx)
    if ssm is None:
        return None
    d, counts = _model(ctx)
    n_layers = counts.mamba_layers(d)
    lanes = ssm["slots_live"] * n_layers / ssm["layer_steps"]  # a decode step
    if kind == "slots_live":
        return lanes
    if kind == "state_roofline":
        kernel = load_json("cellbench", "metrics", params["kernel_metric"] + ".json")
        kernel_ms = _result_ms_per_step(ctx, kernel["params"])
        if not kernel_ms:
            return None
        horizon = int(ctx["facts"]["decode_horizon"])
        step_bytes = counts.scan_state_step_bytes(d, lanes, horizon)
        bandwidth = peaks_for(ctx["facts"]["device_kind"])["hbm_bytes_per_s"]
        least_ms = 1e3 * step_bytes / bandwidth
        ctx.setdefault("notes", {})[params["note"]] = {
            "state_bytes_a_step": step_bytes, "least_ms": least_ms,
            "kernel_ms": kernel_ms, "slots_live": lanes, "horizon": horizon,
        }
        return 100.0 * least_ms / kernel_ms
    raise ValueError(f"unknown kind {kind!r}")
