"""Metrics of an expert layer that holds a share of its router's experts,
from the engine's ledger (`moe` in `GET /debug/goodput`: sums over (expert
layer, decode step) pairs, counted on the device inside `decode_multi` and
fetched with the tokens): `assignments` counts the live tokens' assignments
to the experts this chip holds, `assignments_made` every assignment the
router made for them.

A program without the counters (the parent of the PR that brought them, or a
model that holds every expert, whose `assignments_made` stays 0) gives
nothing: the reader returns None and never raises.

`kind`: `held_assignment_share`.
"""

from __future__ import annotations

from cellbench.readers.expert_layers import _moe_delta


def read(ctx: dict, params: dict):
    try:
        return _read(ctx, params)
    except Exception as e:  # noqa: BLE001: a metric gives nothing, it costs no run
        ctx.setdefault("notes", {})["held_share_error"] = f"{type(e).__name__}: {e}"
        return None


def _read(ctx: dict, params: dict):
    kind = params["kind"]
    if kind != "held_assignment_share":
        raise ValueError(f"unknown kind {kind!r}")
    moe = _moe_delta(ctx)
    if moe is None or moe.get("assignments_made", 0.0) <= 0:
        return None
    return 100.0 * moe["assignments"] / moe["assignments_made"]
