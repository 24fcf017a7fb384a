"""Metrics of a program whose paged layers are of two groups (window layers
that give their pages back beside layers that keep every position): the two
groups' paged decode calls in the device trace, their shares of their
rooflines, and the pools' counters of the engine's ledger (`pool` in `GET
/debug/goodput`: `dynamo_tpu/telemetry/goodput.py` POOL_COUNTERS, counted on
the host where the lane arrays are built).

A group's paged calls are found by what they read (`trace_reduce.finder`): the
two groups' page arrays differ in their block counts, which the server
reports when it is built (`facts`: `num_blocks`, `window_blocks`), so the
metric's `operand` may name `{full_blocks}` or `{window_blocks}` beside the
sizes of the reference's `dims`.

A share of a roofline counts its rows over the traced stretch (the ledger
read at the trace's edges: `expert_layers.ledger_delta(traced=True)`), where
its kernel's time comes from.

A program without the group of counters (the parent of the PR that brought
them: its server reports no `window_blocks` either), a run without a trace,
or a trace in which the operand finds nothing gives nothing: the reader
returns None and never raises.

`kind`: `paged_ms_per_step`, `paged_roofline`, `window_pages_per_lane`,
`pool_in_use_share`.
"""

from __future__ import annotations

import importlib

from cellbench import trace_reduce as tr
from cellbench.manifest import load_json
from cellbench.peaks import peaks_for
from cellbench.readers.device_trace import _dims, _dispatches


def _pool_delta(ctx: dict, traced: bool = False) -> dict | None:
    edges = ("ledger_t0", "ledger_t1") if traced and ctx.get("ledger_t0") and ctx.get("ledger_t1") \
        else ("ledger0", "ledger1")
    a, b = ctx.get(edges[0]) or {}, ctx.get(edges[1]) or {}
    if not isinstance(a.get("pool"), dict) or not isinstance(b.get("pool"), dict):
        return None
    delta = {k: float(b["pool"].get(k, 0.0)) - float(a["pool"].get(k, 0.0)) for k in b["pool"]}
    return delta if delta.get("decode_steps", 0.0) > 0 else None


def _sizes(ctx: dict) -> dict:
    facts = ctx["facts"]
    sizes = dict(_dims(ctx))
    if facts.get("window_blocks"):
        sizes.update(full_blocks=facts["num_blocks"], window_blocks=facts["window_blocks"])
    return sizes


def _paged_ms_per_step(ctx: dict, params: dict):
    red = ctx.get("trace")
    if not red or not red.get("planes"):
        return None
    found = tr.finder(params, _sizes(ctx))
    ops, steps = _dispatches(ctx, params)
    if not steps:
        return None
    ns = sum(e[2] for e in ops if found(e[0]))
    if ns <= 0:
        return None
    return ns / 1e6 / len(red["planes"]) / steps


def read(ctx: dict, params: dict):
    try:
        return _read(ctx, params)
    except Exception as e:  # noqa: BLE001: a metric gives nothing, it costs no run
        ctx.setdefault("notes", {})["page_pool_error"] = f"{type(e).__name__}: {e}"
        return None


def _read(ctx: dict, params: dict):
    kind = params["kind"]
    if kind == "paged_ms_per_step":
        return _paged_ms_per_step(ctx, params)
    d = _dims(ctx)
    if kind == "paged_roofline":
        kernel = load_json("cellbench", "metrics", params["kernel_metric"] + ".json")["params"]
        kernel_ms = _paged_ms_per_step(ctx, kernel)
        there = _pool_delta(ctx, traced=True)
        if not kernel_ms or there is None:
            return None
        counts = importlib.import_module(f"cellbench.counts.{ctx['config']['bench']['counts']}")
        rows = there[params["rows"]] / there["decode_steps"]  # a step
        lanes = there["lane_steps"] / there["decode_steps"]
        layers = d[params["layers"]]
        # the rows read, and the new token's written in each layer
        step_bytes = counts.rows_bytes(d, rows + lanes, layers)
        least_ms = 1e3 * step_bytes / peaks_for(ctx["facts"]["device_kind"])["hbm_bytes_per_s"]
        ctx.setdefault("notes", {})[params["note"]] = {
            "rows_a_step": rows, "lanes": lanes, "layers": layers, "kv_bytes_a_step": step_bytes,
            "least_ms": least_ms, "kernel_ms": kernel_ms,
        }
        return 100.0 * least_ms / kernel_ms
    pool = _pool_delta(ctx)
    if pool is None:
        return None
    if kind == "window_pages_per_lane":
        if pool["lanes_past_window"] <= 0:
            return None
        return pool["window_blocks_past"] / pool["lanes_past_window"]
    if kind == "pool_in_use_share":
        # a block at its group's rows: a window block is as many layers' rows
        # as the model has window layers, a full block as it has full ones
        w, f = d["window_layers"], d["full_layers"]
        held = w * pool["window_in_use_steps"] + f * pool["full_in_use_steps"]
        room = w * pool["window_capacity_steps"] + f * pool["full_capacity_steps"]
        ctx.setdefault("notes", {})["kv_pool_in_use_share"] = {
            "window_share": pool["window_in_use_steps"] / max(1.0, pool["window_capacity_steps"]),
            "full_share": pool["full_in_use_steps"] / max(1.0, pool["full_capacity_steps"]),
            "window_blocks_given_back": pool.get("window_blocks_given_back", 0.0),
        }
        return 100.0 * held / room if room > 0 else None
    raise ValueError(f"unknown kind {kind!r}")
