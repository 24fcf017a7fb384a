"""Metrics from the engine's goodput ledger (`GET /debug/goodput`) and its
Prometheus counters, as the difference between the reading at the window's
opening and the one at its close.

The ledger keeps a count and a mean per dispatch label, so a label's host
time is count x mean; its percentiles sit on a 20% log grid and are not
read. `kind` chooses the quantity; labels are matched by prefix.
"""

from __future__ import annotations

import re


def _labels(ledger: dict, prefixes: list[str]) -> dict:
    return {
        k: v for k, v in ledger["steps_by_label"].items()
        if any(k.startswith(p) for p in prefixes)
    }


def _count(ledger: dict, prefixes: list[str]) -> float:
    return sum(v["count"] for v in _labels(ledger, prefixes).values())


def _sum_ms(ledger: dict, prefixes: list[str]) -> float:
    return sum(v["count"] * v["mean_ms"] for v in _labels(ledger, prefixes).values())


def _prom_sum(text: str, name: str) -> float:
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and not line.startswith("#"):
            try:
                total += float(line.rsplit(" ", 1)[1])
            except (IndexError, ValueError):
                pass
    return total


def read(ctx: dict, params: dict):
    a, b = ctx.get("ledger0"), ctx.get("ledger1")
    if not a or not b:
        return None
    kind = params["kind"]
    if kind == "label_share":
        whole = _count(b, [""]) - _count(a, [""])
        part = _count(b, params["labels"]) - _count(a, params["labels"])
        return 100.0 * part / whole if whole > 0 else None
    if kind == "ms_per_step":
        # decode_multi@H<h>B<b>: one dispatch is h steps
        steps = ms = 0.0
        for label, v in _labels(b, params["labels"]).items():
            m = re.search(r"@H(\d+)", label)
            h = int(m.group(1)) if m else 1
            before = a["steps_by_label"].get(label, {"count": 0, "mean_ms": 0.0})
            steps += h * (v["count"] - before["count"])
            ms += v["count"] * v["mean_ms"] - before["count"] * before["mean_ms"]
        return ms / steps if steps > 0 else None
    if kind == "ms_per_ktok":
        tokens = b["prefill_tokens"] - a["prefill_tokens"]
        ms = _sum_ms(b, params["labels"]) - _sum_ms(a, params["labels"])
        return 1000.0 * ms / tokens if tokens > 0 else None
    if kind == "lane_occupancy":
        # the ledger adds `lanes` and `capacity` (= --max-batch) once per
        # decode-family dispatch and publishes their ratio
        cap = float(ctx["facts"]["max_batch"])
        c0, c1 = (cap * _count(x, params["labels"]) for x in (a, b))
        if c1 <= c0:
            return None
        return 100.0 * (b["occupancy"] * c1 - a["occupancy"] * c0) / (c1 - c0)
    if kind == "prom_counter_delta":
        return _prom_sum(ctx.get("prom1", ""), params["name"]) - _prom_sum(
            ctx.get("prom0", ""), params["name"]
        )
    raise ValueError(f"unknown kind {kind!r}")
