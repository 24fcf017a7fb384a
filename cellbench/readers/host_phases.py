"""Metrics from the program's own phase table: the `phases` object of
`GET /debug/goodput` (`dynamo_tpu/telemetry/trace.py::phase`), as the
difference between the reading at the window's opening (`ledger0`) and the
one at its close (`ledger1`). A program without the table (the parent of
the PR that brought it) gives nothing.

`kind` chooses the quantity:

* `ms_per_count`: the summed `field` (`ms` or `self_ms`) of `phases` over the
  count of the phase `count_of`;
* `ms_per_ktok`: the summed `ms` of `phases` per 1,000 of the ledger's
  `decode_tokens`;
* `first_dispatch_s`: the sum of `ledger0.compile_s_by_label`, the seconds
  that first dispatches took before the window opened.
"""

from __future__ import annotations


def _delta(a: dict, b: dict, name: str, field: str) -> float:
    return b.get(name, {}).get(field, 0) - a.get(name, {}).get(field, 0)


def read(ctx: dict, params: dict):
    a, b = ctx.get("ledger0"), ctx.get("ledger1")
    if not a or not b:
        return None
    kind = params["kind"]
    if kind == "first_dispatch_s":
        table = a.get("compile_s_by_label")
        return float(sum(table.values())) if table else None
    p0, p1 = a.get("phases"), b.get("phases")
    if p0 is None or p1 is None:
        return None
    if "phases" not in ctx.setdefault("notes", {}):
        # the whole table over the window, for a reader of the window line
        ctx["notes"]["phases"] = {
            n: {f: round(_delta(p0, p1, n, f), 3) for f in ("count", "ms", "self_ms")}
            for n in sorted(p1)
        }
    ms = sum(_delta(p0, p1, n, params.get("field", "ms")) for n in params["phases"])
    if kind == "ms_per_count":
        count = _delta(p0, p1, params["count_of"], "count")
        return ms / count if count > 0 else None
    if kind == "ms_per_ktok":
        tokens = b.get("decode_tokens", 0) - a.get("decode_tokens", 0)
        return 1000.0 * ms / tokens if tokens > 0 else None
    raise ValueError(f"unknown kind {kind!r}")
