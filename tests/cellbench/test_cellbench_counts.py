"""The table of peaks and the decode step's counts, against counts worked
by hand for both configurations."""

from __future__ import annotations

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from cellbench.counts import dense_gqa_decode as counts  # noqa: E402
from cellbench.manifest import hf_config  # noqa: E402
from cellbench.peaks import peaks_for  # noqa: E402
from cellbench.reference.dense_gqa import dims  # noqa: E402


def config(name: str) -> dict:
    with open(os.path.join(REPO, "cellbench", "configs", name + ".json")) as f:
        return json.load(f)


# worked by hand from the published sizes
HAND = {
    "mistral7b-int8": {
        # 4096 x (4096 + 2 x 1024) + 4096 x 4096 + 3 x 4096 x 14336
        "layer": 25_165_824 + 16_777_216 + 176_160_768,
        "matmul": 32 * 218_103_808 + 4096 * 32000,
        "kv_bytes_per_position": 2 * 32 * 8 * 128 * 2,
    },
    "qwen25-7b-int8": {
        # 3584 x (3584 + 2 x 512) + 3584 x 3584 + 3 x 3584 x 18944
        "layer": 16_515_072 + 12_845_056 + 203_685_888,
        "matmul": 28 * 233_046_016 + 3584 * 152064,
        "kv_bytes_per_position": 2 * 28 * 4 * 128 * 2,
    },
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_counts_against_hand_worked(name):
    d = dims(hf_config(config(name)))
    hand = HAND[name]
    assert counts.layer_matmul_params(d) == hand["layer"]
    lanes, ctx = 34, 450
    c = counts.step_counts(d, lanes, ctx)
    assert c["weight_bytes"] == hand["matmul"]  # int8: one byte a weight
    kv = lanes * ctx * hand["kv_bytes_per_position"] + lanes * hand["kv_bytes_per_position"]
    assert c["kv_bytes"] == kv
    assert c["bytes"] == hand["matmul"] + kv + lanes * d["hidden"] * 2
    attn = 4 * lanes * d["layers"] * d["heads"] * d["head_dim"] * ctx
    assert c["ops"] == 2 * lanes * hand["matmul"] + attn


def test_mistral_step_by_hand_is_bound_by_bytes():
    d = dims(hf_config(config("mistral7b-int8")))
    c = counts.step_counts(d, 34, 450)
    assert c["bytes"] == 9_120_530_432
    assert c["ops"] == 491_528_388_608
    least, bound = counts.least_seconds(c, peaks_for("TPU v5 lite"))
    assert bound == "bytes"
    assert least == pytest.approx(9_120_530_432 / 819e9)
    # PR 23 read 135.8 ms a step on the device: about a twelfth of it is needed
    assert 100 * least / 0.1358 == pytest.approx(8.2, abs=0.1)


@pytest.mark.parametrize("name", sorted(HAND))
@pytest.mark.parametrize("lanes,ctx", [(1, 16), (34, 450), (64, 4096)])
def test_needed_bytes_never_exceed_what_any_program_must_move(name, lanes, ctx):
    """Counts hold what the algorithm needs and nothing a program adds, so
    they are at most the weights once plus every live position once."""
    d = dims(hf_config(config(name)))
    c = counts.step_counts(d, lanes, ctx)
    assert c["bytes"] <= HAND[name]["matmul"] + (lanes * (ctx + 1)) * HAND[name][
        "kv_bytes_per_position"] + lanes * d["hidden"] * 2
    assert c["ops"] > 0 and c["bytes"] > c["weight_bytes"]


def test_peaks_name_their_source_and_refuse_an_unknown_kind():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks_for("cpu")
