"""Traffic that every seed offers alike: same multisets, whole and per
block, another order, exactly the stated count."""

from __future__ import annotations

import json
import os
import sys
from collections import Counter

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from cellbench.generators import stratified_open_loop as gen  # noqa: E402

MIXES = ["chat-steady", "chat-sat"]
SEEDS = [0, 1, 7, 2147483647, 2147483999, 4000000123]


def mix(name: str) -> dict:
    with open(os.path.join(REPO, "cellbench", "traffic", name + ".json")) as f:
        return json.load(f)


def rounded(values):
    return Counter(round(v, 9) for v in values)


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("seed", SEEDS)
def test_every_seed_offers_the_same_multisets(name, seed):
    m = mix(name)
    base = gen.generate(m, 12345, 51.0, 32000)
    reqs = gen.generate(m, seed, 51.0, 32000)
    assert len(reqs) == len(base) == gen.n_requests(m, 51.0)
    assert len(reqs) == round(m["rate_rps"] * (m["ramp_s"] + 51.0))
    # ramp and window are whole blocks at the benchmark's run_seconds
    size = m["block_requests"]
    assert len(reqs) % size == 0
    ramp = [r for r in reqs if r["due_s"] < m["ramp_s"]]
    assert len(ramp) % size == 0 and len(ramp) == round(m["rate_rps"] * m["ramp_s"])
    for key in ("prompt_tokens", "output_tokens"):
        assert Counter(r[key] for r in reqs) == Counter(r[key] for r in base)
    # which prompt goes with which output is the mix's, not the seed's
    pair = lambda r: (r["prompt_tokens"], r["output_tokens"])  # noqa: E731
    assert Counter(map(pair, reqs)) == Counter(map(pair, base))
    assert rounded(r["gap_s"] for r in reqs) == rounded(r["gap_s"] for r in base)
    # per block too: every whole block holds the block's multiset
    sets = gen.block_multisets(m)
    for b in range(len(reqs) // size):
        block = [r for r in reqs if r["block"] == b]
        assert Counter(map(pair, block)) == Counter(
            zip(sets["prompt_tokens"], sets["output_tokens"]))
        assert rounded(r["gap_s"] for r in block) == rounded(sets["gaps_s"])
    # in another order, with other token ids
    assert [r["prompt_tokens"] for r in reqs] != [r["prompt_tokens"] for r in base]
    assert reqs[0]["token_ids"] != base[0]["token_ids"]
    assert all(len(r["token_ids"]) == r["prompt_tokens"] for r in reqs)
    assert all(3 <= t < 32000 for r in reqs for t in r["token_ids"])


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    m = mix(name)
    assert gen.generate(m, 99, 20.0, 1000) == gen.generate(m, 99, 20.0, 1000)


@pytest.mark.parametrize("name", MIXES)
def test_block_spans_exactly_its_share_of_time(name):
    m = mix(name)
    sets = gen.block_multisets(m)
    assert sum(sets["gaps_s"]) == pytest.approx(m["block_requests"] / m["rate_rps"], rel=1e-12)
    reqs = gen.generate(m, 5, 51.0, 32000)
    size = m["block_requests"]
    # the last request of block b is due at (b + 1) blocks' worth of time
    last = reqs[size - 1]["due_s"]
    assert last < size / m["rate_rps"] < reqs[size]["due_s"]
    assert last == pytest.approx(size / m["rate_rps"], abs=min(sets["gaps_s"]))
    assert all(a["due_s"] <= b["due_s"] for a, b in zip(reqs, reqs[1:]))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_stay_inside_their_clips(name):
    m = mix(name)
    sets = gen.block_multisets(m)
    for key in ("prompt_tokens", "output_tokens"):
        assert min(sets[key]) >= m[key]["min"] and max(sets[key]) <= m[key]["max"]
    # the grid keeps the distribution's median between its middle points
    mid = sorted(sets["prompt_tokens"])
    half = len(mid) // 2
    assert mid[half - 1] <= m["prompt_tokens"]["median"] <= mid[half]


@pytest.mark.parametrize("dist", [
    {"dist": "exponential"}, {"dist": "gamma_cv", "cv": 3.0},
    {"dist": "lognormal", "median": 100.0, "sigma": 0.5},
    {"dist": "constant", "value": 3.0},
])
def test_quantile_grids_rise_and_count(dist):
    grid = gen.quantile_grid(dist, 32)
    assert len(grid) == 32 and all(a <= b for a, b in zip(grid, grid[1:]))
    assert all(v >= 0 for v in grid)


def test_gamma_grid_has_its_coefficient_of_variation():
    grid = gen.quantile_grid({"dist": "gamma_cv", "cv": 1.0}, 400)
    expo = gen.quantile_grid({"dist": "exponential"}, 400)
    assert grid == pytest.approx(expo, rel=1e-3, abs=1e-4)


def test_unknown_distribution_raises():
    with pytest.raises(ValueError):
        gen.quantile_grid({"dist": "pareto"}, 4)
