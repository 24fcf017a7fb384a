"""End-to-end arithmetic on client-side timestamps (no I/O, no clock).

A request is a dict with `due` (when it was due to be sent), `sent`,
`tokens` (the arrival time of each output token, in order; tokens that came
in one chunk share a time), `done` (the stream ended with its requested
count) and `error`. All times are on one monotonic clock, in seconds. The
window is [w0, w1). Each quantity is taken over everything that falls inside
the window: a rate over all tokens and all of the window, a tail over all
gaps. (The TTFT and inter-token arithmetic follows
`benchmarks/perf_sweep.py`, which times from the send; here a request is
timed from when it was due.)
"""

from __future__ import annotations

import math


def percentile(values: list[float], q: float) -> float:
    """Linear-interpolated percentile, q in [0, 100]."""
    if not values:
        raise ValueError("no values")
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def in_window(t: float, w0: float, w1: float) -> bool:
    return w0 <= t < w1


def out_tok_s(requests: list[dict], w0: float, w1: float) -> float:
    """Output tokens delivered inside the window, over the window."""
    n = sum(1 for r in requests for t in r["tokens"] if in_window(t, w0, w1))
    return n / (w1 - w0)


def tpots_ms(requests: list[dict], w0: float, w1: float) -> list[float]:
    """(last token - first token) / (tokens - 1) of every request that
    completed inside the window with two tokens or more."""
    out = []
    for r in requests:
        tok = r["tokens"]
        if r.get("done") and len(tok) >= 2 and in_window(tok[-1], w0, w1):
            out.append((tok[-1] - tok[0]) / (len(tok) - 1) * 1e3)
    return out


def ttfts_ms(requests: list[dict], w0: float, w1: float) -> list[float]:
    """First token minus due time, of every request whose first token
    arrived inside the window."""
    return [
        (r["tokens"][0] - r["due"]) * 1e3
        for r in requests
        if r["tokens"] and in_window(r["tokens"][0], w0, w1)
    ]


def gaps_ms(requests: list[dict], w0: float, w1: float) -> list[float]:
    """Every inter-token gap that ended inside the window, pooled."""
    out = []
    for r in requests:
        tok = r["tokens"]
        for a, b in zip(tok, tok[1:]):
            if in_window(b, w0, w1):
                out.append((b - a) * 1e3)
    return out


def tail_mean(values: list[float], share: float) -> float:
    """Mean of the slowest `share` of the values (at least one)."""
    if not values:
        raise ValueError("no values")
    k = max(1, math.ceil(len(values) * share))
    return sum(sorted(values)[-k:]) / k


def lateness_ms(requests: list[dict], w0: float, w1: float) -> list[float]:
    """How late the generator sent each request due inside the window."""
    return [
        (r["sent"] - r["due"]) * 1e3
        for r in requests
        if r.get("sent") is not None and in_window(r["due"], w0, w1)
    ]


def attempted_failed(requests: list[dict], w0: float, w1: float) -> tuple[int, int]:
    """Requests due inside the window, and those of them whose stream was
    refused or broke. A stream cut by the window's end is neither completed
    nor failed."""
    due = [r for r in requests if in_window(r["due"], w0, w1)]
    return len(due), sum(1 for r in due if r.get("error"))


def summarise(requests: list[dict], w0: float, w1: float) -> dict:
    """Every client-side quantity a metric's reader may ask for."""
    out: dict = {"out_tok_s": out_tok_s(requests, w0, w1)}
    tp, tt, gp = (f(requests, w0, w1) for f in (tpots_ms, ttfts_ms, gaps_ms))
    late = lateness_ms(requests, w0, w1)
    out["n_tpot"], out["n_ttft"], out["n_gaps"] = len(tp), len(tt), len(gp)
    if tp:
        out["tpot_p50_ms"] = percentile(tp, 50)
    if tt:
        out["ttft_p50_ms"] = percentile(tt, 50)
        out["ttft_p90_ms"] = percentile(tt, 90)
    if gp:
        out["gap_tail5_ms"] = tail_mean(gp, 0.05)
        out["gap_p99_ms"] = percentile(gp, 99)
    if late:
        out["gen_lateness_p99_ms"] = percentile(late, 99)
    return out


def live_lanes_context(requests: list[dict], a: float, b: float,
                       samples: int = 40) -> dict | None:
    """Mean number of streams in decode (first token received, last not yet)
    over [a, b), and the mean context (prompt plus tokens so far) of such a
    stream: what a decode step's keys-and-values traffic follows."""
    lanes = ctx = 0.0
    for i in range(samples):
        t = a + (b - a) * (i + 0.5) / samples
        for r in requests:
            tok = r["tokens"]
            if not tok or tok[0] > t:
                continue
            if len(tok) >= r["output_tokens"] and tok[-1] <= t:
                continue
            lanes += 1
            ctx += r["prompt_tokens"] + sum(1 for x in tok if x <= t)
    if not lanes:
        return None
    return {"lanes": lanes / samples, "context": ctx / lanes}
