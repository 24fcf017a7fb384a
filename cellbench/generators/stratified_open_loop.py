"""Open-loop traffic that every seed offers alike.

The number of requests is fixed by the rate and the time, never drawn.
Prompt lengths, output lengths and inter-arrival gaps are each the quantile
grid of their distribution (the mid-point quantiles (i + 0.5) / m of a block
of m consecutive requests), so every block of `block_requests` requests holds
the same multiset of lengths and of gaps, and spans exactly m / rate seconds.
Which prompt goes with which output is fixed by the mix (`pairing_seed`), the
same in every block and for every seed: a long prompt under a long answer
holds more of the cache for longer than the same two lengths crossed, so a
pairing drawn by the seed would change the work (the first six runs on the
chip read `tpot_p50_ms` 3.3% apart that way). The seed permutes the pairs and
the gaps within each block and draws the token ids. So every seed offers the
same requests and the same arrival gaps in every stretch of the run, in a
different order.

All parameters come from the mix's file under `cellbench/traffic/`.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist


def quantile_grid(spec: dict, m: int) -> list[float]:
    """The m mid-point quantiles of the distribution `spec` describes."""
    qs = [(i + 0.5) / m for i in range(m)]
    dist = spec["dist"]
    if dist == "lognormal":
        nd = NormalDist()
        out = [
            spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(q)) for q in qs
        ]
    elif dist == "exponential":
        out = [-math.log(1.0 - q) for q in qs]
    elif dist == "gamma_cv":
        # a gamma with the given coefficient of variation, by inverting its
        # CDF numerically on a fine grid (shape k = 1 / cv**2)
        k = 1.0 / (spec["cv"] ** 2)
        out = [_gamma_inv(k, q) for q in qs]
    elif dist == "constant":
        out = [float(spec["value"])] * m
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    if "min" in spec or "max" in spec:
        lo = spec.get("min", -math.inf)
        hi = spec.get("max", math.inf)
        out = [min(max(v, lo), hi) for v in out]
    return out


def _gamma_inv(k: float, q: float) -> float:
    lo, hi = 0.0, 50.0 * max(k, 1.0)
    for _ in range(80):
        mid = (lo + hi) / 2
        if _gamma_cdf(k, mid) < q:
            lo = mid
        else:
            hi = mid
    return (lo + hi) / 2


def _gamma_cdf(k: float, x: float) -> float:
    # regularised lower incomplete gamma by its series
    if x <= 0:
        return 0.0
    term = total = 1.0 / k
    n = 1
    while abs(term) > 1e-14 * abs(total) and n < 10000:
        term *= x / (k + n)
        total += term
        n += 1
    return total * math.exp(-x + k * math.log(x) - math.lgamma(k))


def block_multisets(mix: dict) -> dict:
    """The multiset every block holds: prompt lengths, output lengths and
    inter-arrival gaps (seconds, mean exactly 1 / rate)."""
    m = int(mix["block_requests"])
    rate = float(mix["rate_rps"])
    prompts = [int(round(v)) for v in quantile_grid(mix["prompt_tokens"], m)]
    outputs = [int(round(v)) for v in quantile_grid(mix["output_tokens"], m)]
    raw = quantile_grid(mix["interarrival"], m)
    scale = (m / rate) / sum(raw)
    random.Random(int(mix.get("pairing_seed", 0))).shuffle(outputs)
    return {
        "prompt_tokens": prompts,
        "output_tokens": outputs,  # outputs[i] is the answer to prompts[i]
        "gaps_s": [g * scale for g in raw],
    }


def n_requests(mix: dict, seconds: float) -> int:
    """Requests due in `ramp_s` + `seconds`: fixed by rate and time. A mix
    whose ramp and window are each a whole number of blocks (the shipped ones
    are, at the benchmark's `run_seconds`) gives every seed the same multiset
    over the whole run and over the window."""
    return int(round(float(mix["rate_rps"]) * (float(mix["ramp_s"]) + seconds)))


def generate(mix: dict, seed: int, seconds: float, vocab_size: int) -> list[dict]:
    """The requests of one run, in arrival order. `due_s` counts from the
    start of the ramp; the window opens at `ramp_s`. Token ids avoid the
    three special ids (0, 1, 2) of the benchmark's word-level tokenizer."""
    m = int(mix["block_requests"])
    total = n_requests(mix, seconds)
    sets = block_multisets(mix)
    rng = random.Random(int(seed))
    out: list[dict] = []
    # arrivals sit half the smallest gap before their cumulative time, so a
    # block's last one is safely inside its block whatever the rounding
    due = -min(sets["gaps_s"]) / 2
    for b in range(math.ceil(total / m)):
        pairs = list(zip(sets["prompt_tokens"], sets["output_tokens"]))
        gaps = sets["gaps_s"][:]
        rng.shuffle(pairs)
        rng.shuffle(gaps)
        for i in range(m):
            due += gaps[i]
            out.append({
                "index": b * m + i,
                "block": b,
                "due_s": due,
                "gap_s": gaps[i],
                "prompt_tokens": pairs[i][0],
                "output_tokens": pairs[i][1],
                "token_ids": [
                    rng.randrange(3, vocab_size) for _ in range(pairs[i][0])
                ],
            })
    return out[:total]
