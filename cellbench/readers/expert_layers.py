"""Metrics of a latent-attention, sparse-expert program: its two kernels'
time in the device trace, their shares of their rooflines, and the experts'
counters of the engine's ledger (`moe` in `GET /debug/goodput`: sums over
(expert layer, decode step) pairs, counted on the device inside
`decode_multi` and fetched with the tokens).

The trace's operation records carry the HLO instruction and no
`jax.named_scope` (`trace_reduce.load_xplane` keeps names; XLA's own
grouped-product call has lost its scope by then anyway: its `op_name` is
`ragged-dot-none`), so an operation is found by a pattern on its name, given
in the metric's file. `{heads}`, `{kv_rank}` and the other sizes of the
reference's `dims` may stand in a pattern.

A program without the counters (the parent of the PR that brought them), a
run without a trace, or a trace in which the pattern finds nothing gives
nothing: the reader returns None and never raises.

`kind`: `pattern_ms_per_step`, `experts_roofline`, `latent_roofline`,
`experts_touched_per_layer`, `load_max_over_mean`.
"""

from __future__ import annotations

import importlib
import re

from cellbench.manifest import hf_config, load_json
from cellbench.peaks import peaks_for
from cellbench.readers.device_trace import _dispatches


def _model(ctx: dict):
    bench = ctx["config"]["bench"]
    ref = importlib.import_module(f"cellbench.reference.{bench['reference']}")
    counts = importlib.import_module(f"cellbench.counts.{bench['counts']}")
    return ref.dims(hf_config(ctx["config"])), counts


def _moe_delta(ctx: dict) -> dict | None:
    a, b = ctx.get("ledger0") or {}, ctx.get("ledger1") or {}
    if not isinstance(a.get("moe"), dict) or not isinstance(b.get("moe"), dict):
        return None
    delta = {k: float(b["moe"].get(k, 0.0)) - float(a["moe"].get(k, 0.0)) for k in b["moe"]}
    return delta if delta.get("layer_steps", 0.0) > 0 else None


def _pattern_ms_per_step(ctx: dict, params: dict):
    red = ctx.get("trace")
    if not red or not red.get("planes"):
        return None
    d, _ = _model(ctx)
    rx = re.compile(params["pattern"].format(**d))
    ops, steps = _dispatches(ctx, params)
    if not steps:
        return None
    ns = sum(e[2] for e in ops if rx.search(e[0]))
    if ns <= 0:
        return None
    return ns / 1e6 / len(red["planes"]) / steps


def _metric_ms(ctx: dict, name: str):
    return _pattern_ms_per_step(ctx, load_json("cellbench", "metrics", name + ".json")["params"])


def read(ctx: dict, params: dict):
    try:
        return _read(ctx, params)
    except Exception as e:  # noqa: BLE001: a metric gives nothing, it costs no run
        ctx.setdefault("notes", {})["expert_layers_error"] = f"{type(e).__name__}: {e}"
        return None


def _read(ctx: dict, params: dict):
    kind = params["kind"]
    if kind == "pattern_ms_per_step":
        return _pattern_ms_per_step(ctx, params)
    d, counts = _model(ctx)
    bandwidth = peaks_for(ctx["facts"]["device_kind"])["hbm_bytes_per_s"]
    if kind == "latent_roofline":
        kernel_ms = _metric_ms(ctx, params["kernel_metric"])
        live = ctx["client"].get("live")
        if not kernel_ms or not live:
            return None
        c = counts.step_counts(d, live["lanes"], live["context"])
        least_ms = 1e3 * c["kv_bytes"] / bandwidth
        ctx.setdefault("notes", {})[params["note"]] = {
            "kv_bytes": c["kv_bytes"], "least_ms": least_ms, "kernel_ms": kernel_ms,
            "lanes": live["lanes"], "context": live["context"],
        }
        return 100.0 * least_ms / kernel_ms
    moe = _moe_delta(ctx)
    if moe is None:
        return None
    n_moe = max(0, d["layers"] - d["first_dense"])
    touched = moe["experts_touched"] / moe["layer_steps"]  # a layer, a step
    if kind == "experts_touched_per_layer":
        return touched
    if kind == "load_max_over_mean":
        if moe["assignments"] <= 0:
            return None
        return moe["max_expert_load"] * d["experts"] / moe["assignments"]
    if kind == "experts_roofline":
        kernel_ms = _metric_ms(ctx, params["kernel_metric"])
        if not kernel_ms:
            return None
        step_bytes = counts.experts_bytes(d, touched * n_moe)
        least_ms = 1e3 * step_bytes / bandwidth
        ctx.setdefault("notes", {})[params["note"]] = {
            "expert_bytes_a_step": step_bytes, "least_ms": least_ms,
            "kernel_ms": kernel_ms, "experts_touched_a_layer": touched,
        }
        return 100.0 * least_ms / kernel_ms
    raise ValueError(f"unknown kind {kind!r}")
