"""What PR 52 adds to the benchmark: the window-and-full attention,
sparse-expert configuration's file against the catalog, its counts against
`param_count` and against counts worked by hand (the window layers' rows from
a mean among them: a bound no split of the lanes undercuts), its mix, its nine
per-layer metrics through their readers on a made-up run, its check's limit
against the readings beside it, what the cell resolves to, and every accepted
list of `BENCHMARK.json` as a prefix of the new one."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from cellbench import manifest  # noqa: E402
from cellbench.counts import afmoe_decode as counts  # noqa: E402
from cellbench.generators import stratified_open_loop as gen  # noqa: E402
from cellbench.manifest import hf_config  # noqa: E402
from cellbench.peaks import peaks_for  # noqa: E402
from cellbench.readers import device_trace, expert_layers, host_device_join, page_pool  # noqa: E402
from cellbench.reference import afmoe as ref  # noqa: E402

CELL = "trinity-large-bf16-l5-e32.long-short-steady"
CONFIG = "trinity-large-bf16-l5-e32"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READERS = {
    "window_attn_ms": page_pool, "full_attn_ms": page_pool,
    "window_attn_roofline": page_pool, "full_attn_roofline": page_pool,
    "window_pages_per_lane": page_pool, "kv_pool_in_use_share": page_pool,
    "afmoe_experts_ms": expert_layers, "afmoe_experts_roofline": expert_layers,
    "afmoe_experts_touched": expert_layers,
}
REDUCED = ["bos_token_id", "eos_token_id", "layer_types", "max_position_embeddings", "num_dense_layers",
           "num_experts", "num_hidden_layers", "vocab_size"]
# the accepted benchmark's lists as PR 50 left them (the parent of PR 52)
ACCEPTED = {
    "configs": ["mistral7b-int8", "qwen25-7b-int8", "joyai-flash-bf16-l5", "jamba2-3b-bf16",
                "lfm2-8b-a1b-bf16-l16", "nemotron3-super-bf16-l11-e128"],
    "workloads": ["mistral7b-int8.chat-steady", "qwen25-7b-int8.chat-sat", "joyai-flash-bf16-l5.reason-steady",
                  "jamba2-3b-bf16.think-steady", "lfm2-8b-a1b-bf16-l16.assist-steady",
                  "nemotron3-super-bf16-l11-e128.plan-steady"],
    "end_to_end": ["out_tok_s", "tpot_p50_ms", "setup_s"],
    "per_layer": [
        "gen_lateness_p99_ms",
        "ttft_p50_ms",
        "ttft_p90_ms",
        "gap_tail5_ms",
        "gap_p99_ms",
        "mixed_step_share",
        "lane_occupancy",
        "preemptions",
        "decode_step_ms",
        "prefill_ms_per_ktok",
        "decode_device_ms",
        "decode_step_mfu",
        "copy_share",
        "attn_kernel_ms",
        "device_idle_share",
        "frontend_pre_ms",
        "frontend_post_ms_per_ktok",
        "queue_wait_ms",
        "prefill_wait_ms",
        "loop_host_ms_per_dispatch",
        "idle_packer_share",
        "idle_frontend_share",
        "idle_dispatch_share",
        "attn_kernel_roofline",
        "first_dispatch_s",
        "moe_experts_ms",
        "mla_attn_ms",
        "moe_experts_roofline",
        "mla_attn_roofline",
        "experts_touched_per_layer",
        "expert_load_max_over_mean",
        "ssm_step_ms",
        "ssm_step_roofline",
        "state_slots_live",
        "prefill_scan_ms_per_ktok",
        "idle_hop_share",
        "idle_upload_share",
        "idle_enqueue_share",
        "idle_fetch_lead_share",
        "idle_fetch_drain_share",
        "launch_upload_ms",
        "launch_enqueue_ms",
        "upload_arrays_per_dispatch",
        "routed_ffn_ms",
        "routed_ffn_roofline",
        "routed_experts_touched",
        "short_conv_ms",
        "conv_slots_live",
        "chained_dispatch_share",
        "held_experts_ms",
        "held_experts_roofline",
        "held_experts_touched",
        "held_assignment_share",
        "ssm2_step_ms",
        "ssm2_step_roofline",
        "ssm2_prefill_ms_per_ktok"
    ],
}


def config() -> dict:
    return manifest.load_json("cellbench", "configs", CONFIG + ".json")


def dims() -> dict:
    return ref.dims(hf_config(config()))


def catalog_row() -> dict:
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        return next(r for r in map(json.loads, f) if r["name"] == "Trinity-Large-Preview")


def metric(name: str) -> dict:
    return manifest.load_json("cellbench", "metrics", name + ".json")["params"]


def read(ctx: dict, name: str):
    return READERS[name].read(ctx, metric(name))


def test_configuration_file_holds_the_catalogs_keys_but_the_reduced():
    row = catalog_row()
    doc = config()
    reduced = doc["bench"]["reduced"]
    assert doc["bench"]["source"] == row["source_url"]
    assert sorted(reduced) == REDUCED
    for key, value in row["config"].items():
        if key in reduced:
            assert doc[key] != value, key
        else:
            assert doc[key] == value, key
    # the cut: one leading dense layer and one whole period of expert layers
    assert doc["num_hidden_layers"] == 5 and doc["num_dense_layers"] == 1
    assert doc["layer_types"] == row["config"]["layer_types"][:5] == [
        "sliding_attention", "sliding_attention", "sliding_attention", "full_attention", "sliding_attention"]
    assert (doc["num_experts"], doc["num_experts_published"], doc["first_held_expert"], doc["expert_share_chips"]) == (32, 256, 0, 8)
    assert (doc["vocab_size"], doc["vocab_size_published"]) == (25024, 200192) and 8 * 25024 == 200192
    assert doc["max_position_embeddings"] == 24576
    # no width is cut: hidden, heads, the dense and the experts' widths, 4 a
    # token, the router's 256 outputs, the window
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "num_experts_per_tok", "sliding_window", "num_shared_experts",
                "route_scale", "rope_theta", "rms_norm_eps"):
        assert doc[key] == row["config"][key], key
    assert doc["tie_word_embeddings"] is False and doc["torch_dtype"] == "bfloat16"
    assert doc["bench"]["server"]["env"] == {"DYN_CHUNK_BUDGET": "512"}
    assert doc["bench"]["server"]["args"] == ["--context-length", "24576", "--max-batch", "64"]
    assert doc["bench"]["check"]["controls"] == ["int8_weights", "int8_kv"]
    said = " ".join(doc["bench"]["assumed"])
    for what in ("q and k norms by head", "gate", "expert_bias", "1e-20", "depth-scaled", "checkpoint names",
                 "untried", "torch_dtype bfloat16", "num_experts_published"):
        assert what in said, what
    for what in ("96 TPU v5e chips", "12 pipeline stages", "8 chips a stage", "not written"):
        assert what in doc["bench"]["deployment"], what
    entry = next(c for c in manifest.load_json("BENCHMARK.json")["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"] and sorted(entry["reduced"]) == REDUCED
    assert entry["file"] == "cellbench/configs/" + CONFIG + ".json"
    # what test_cellbench_manifest.py holds for every configuration, but its
    # test of a key's last letters (tests/conftest.py says why): no width is cut
    assert doc["bench"]["name"] == CONFIG and doc["bench"]["check"]["tolerance_rms_rel"] > 0
    for key in ("assumed", "server", "reference", "counts", "weights_seed"):
        assert key in doc["bench"]
    for key in entry["reduced"]:
        assert not key.endswith(("_dim", "_rank")) and key not in (
            "hidden_size", "intermediate_size", "moe_intermediate_size", "num_experts_per_tok")
    probes = doc["bench"]["check"]["probes"]
    # short sequences through a packed prefill and the horizon, and long ones
    # that cross the window in every program
    assert [(p["count"], p["prompt_tokens"]) for p in probes] == [(2, 60), (2, 4700)]
    assert probes[0]["output_tokens"] == 32 and 64 <= probes[1]["output_tokens"] <= 128
    assert probes[1]["prompt_tokens"] > doc["sliding_window"] + 512


def test_counts_against_param_count_whole_and_cut():
    """The counts' own sum of every parameter is the family's `param_count`:
    398,635,286,016 for the catalog's row, 4,321,903,872 for the cut, which
    is ISSUE 52's count to the digit."""
    from dynamo_tpu.models import afmoe

    whole = catalog_row()["config"]
    d = ref.dims(whole)
    assert counts.param_count(d) == 398_635_286_016
    assert counts.param_count(d) == afmoe.param_count(afmoe.AfmoeConfig.from_hf_dict(whole))
    cut = hf_config(config())
    d = ref.dims(cut)
    assert counts.param_count(d) == 4_321_903_872
    assert counts.param_count(d) == afmoe.param_count(afmoe.AfmoeConfig.from_hf_dict(cut))
    # 2 x 4,321,903,872 bytes: over half of the chip on weights alone
    assert 2 * counts.param_count(d) > 0.5 * peaks_for("TPU v5 lite")["hbm_bytes"]


def test_counts_against_hand_worked():
    import random

    d = dims()
    assert (d["layers"], d["window_layers"], d["full_layers"], counts.expert_layers(d)) == (5, 4, 1, 4)
    assert (d["window"], d["max_context"], d["experts"], d["router_experts"]) == (4096, 24576, 32, 256)
    # q, the gate and o 3072 x 6144 each, k and v 3072 x 1024, two norms of 128
    assert counts.attention_params(d) == 3 * 18_874_368 + 2 * 3_145_728 + 256 == 62_914_816
    assert counts.expert_params(d) == 3 * 3072 * 3072 == 28_311_552
    assert counts.expert_layer_params(d) == 786_432 + 256 + 28_311_552
    # an expert layer with its attention, its four norms and its 32 held experts
    assert counts.attention_params(d) + 4 * 3072 + counts.expert_layer_params(d) == 92_025_344
    assert 92_025_344 + 32 * 28_311_552 == 997_995_008
    # keys and values: 2 planes x 8 heads x 128 = 2,048 values, 4,096 bytes a token and layer
    assert counts.kv_values_per_token_layer(d) == 2048
    assert counts.rows_bytes(d, 12_000, 1) == 12_000 * 4096  # a session's full layer: 49 MB
    assert counts.rows_bytes(d, 4096, 4) == 4 * 4096 * 4096  # its four window layers: 67 MB
    # 50 tokens touch 17.4 of a layer's 32 held experts under an even router
    assert counts.expected_experts_touched(d, 50) == pytest.approx(32 * (1 - (252 / 256) ** 50))
    assert 17.3 < counts.expected_experts_touched(d, 50) < 17.5
    assert counts.experts_bytes(d, 4 * 32) == 4 * 32 * 28_311_552 * 2
    # the window layers' rows from a mean: the least any split can read
    assert counts.window_rows_at_least(d, 50, 10_000) == 20 * 4096 + 4096  # 500,000 = 20 x 24,576 + 8,480
    assert counts.window_rows_at_least(d, 40, 300) == 4096  # 12,000 tokens may all be one lane's
    assert counts.window_rows_at_least(d, 1, 300) == 300 and counts.window_rows_at_least(d, 0, 0) == 0
    rng = random.Random(5)
    for _ in range(200):
        lanes = rng.randrange(1, 65)
        ctx = [rng.choice((rng.randrange(1, 900), rng.randrange(4000, 24_577))) for _ in range(lanes)]
        exact = sum(min(c, 4096) for c in ctx)
        mean = sum(ctx) / lanes
        assert counts.window_rows_at_least(d, lanes, mean) <= exact <= lanes * min(mean, 4096) + 1e-6
    # the cell's mix: 32 lanes at 14,000 and 18 at 700: the bound is 60% of
    # what the step reads, min(mean, window) 143% of it
    lanes, mean = 50, (32 * 14_000 + 18 * 700) / 50
    exact = 32 * 4096 + 18 * 700
    assert counts.window_rows_at_least(d, lanes, mean) / exact == pytest.approx(0.55, abs=0.06)
    assert lanes * min(mean, 4096) / exact == pytest.approx(1.43, abs=0.03)
    c = counts.step_counts(d, lanes, mean)
    always = 5 * 62_914_816 + 3 * 3072 * 12288 + 4 * (786_432 + 256 + 28_311_552) + 3072 * 25024
    touched = 4 * counts.expected_experts_touched(d, lanes)
    assert c["experts_touched"] == pytest.approx(touched)
    assert c["weight_bytes"] == pytest.approx(2 * always + touched * 28_311_552 * 2)
    rows = counts.window_rows_at_least(d, lanes, mean)
    assert c["kv_bytes"] == pytest.approx((lanes * mean + 4 * rows) * 4096 + lanes * 5 * 4096)
    assert c["bytes"] == pytest.approx(c["weight_bytes"] + c["kv_bytes"] + lanes * 3072 * 2)
    assert c["ops"] == pytest.approx(
        2 * lanes * (always + 4 * 0.5 * 28_311_552) + 4 * 48 * 128 * (lanes * mean + 4 * rows))
    least, bound = counts.least_seconds(c, peaks_for("TPU v5 lite"))
    assert bound == "bytes" and 0.004 < least < 0.012


def test_mix_is_what_the_issue_names():
    mix = manifest.Cell(CELL).mix
    plan = manifest.load_json("cellbench", "traffic", "plan-steady.json")
    assert mix["generator"] == "stratified_open_loop" and mix["temperature"] == 0.7
    # the open loop: plan-steady's lengths, so two configurations stand under one short mix
    for key in ("prompt_tokens", "output_tokens", "interarrival", "block_requests", "pairing_seed",
                "trace_offset_s", "trace_seconds"):
        assert mix[key] == plan[key], key
    assert "top_p" not in mix and "top_k" not in mix
    sets = gen.block_multisets(mix)
    assert min(sets["prompt_tokens"]) == 41 and max(sets["prompt_tokens"]) == 1602
    assert min(sets["output_tokens"]) == 205 and max(sets["output_tokens"]) == 1280
    # the resident sessions: lingering streams started before the reference is waited for
    phases = mix["warmup"]
    assert [p.get("check_group") for p in phases if "check_group" in p] == [0, 1]
    assert phases[-1] == {"await_reference": True}
    sessions = phases[-2]
    assert sessions["linger"] is True
    prompts = [r["prompt_tokens"] for r in sessions["requests"]]
    assert 24 <= len(prompts) <= 32 and prompts == sorted(prompts)
    assert prompts[0] == 4608 and prompts[-1] == 7680 and sum(prompts) / len(prompts) == pytest.approx(6144, abs=1)
    assert min(prompts) >= config()["sliding_window"] + 512  # every one past the window when its prefill ends
    steps = {b - a for a, b in zip(prompts, prompts[1:])}
    assert max(steps) - min(steps) <= 1  # evenly spaced
    assert {r["output_tokens"] for r in sessions["requests"]} == {16384}
    assert max(prompts) + 16384 <= config()["max_position_embeddings"]
    # the rate is a whole number of blocks of 15 in the 51 s window, the ramp
    # whole blocks too and at least the longest short stream's duration
    blocks = mix["rate_rps"] * 51 / 15
    assert abs(blocks - round(blocks)) < 1e-9
    ramp_blocks = mix["ramp_s"] * mix["rate_rps"] / 15
    assert abs(ramp_blocks - round(ramp_blocks)) < 1e-9
    sweep = manifest.load_json("cellbench", "sweeps", CELL + ".json")
    assert mix["rate_rps"] == pytest.approx(sweep["cell_rate_rps"])
    assert mix["rate_rps"] <= 0.8 * sweep["highest_sustained_rate_rps"] + 1e-9
    assert mix["rate_rps"] + 15 / 51 > 0.8 * sweep["highest_sustained_rate_rps"]
    assert mix["ramp_s"] >= sweep["longest_stream_s"]
    assert all(abs(s["rate_rps"] * 51 / 15 - round(s["rate_rps"] * 51 / 15)) < 1e-3 for s in sweep["steps"])
    # no session may end before the window closes: the tokens it is asked for
    # outlast warm-up, ramp and window at the measured step
    assert sweep["session_tokens_until_the_window_closes"] < 16384
    # the rehearsal keeps the shape at the toy's size
    small = mix["rehearsal"]["warmup"]
    assert small[-1] == {"await_reference": True} and small[-2]["linger"] is True
    assert len(small[-2]["requests"]) == 4


def test_the_limit_lies_between_the_readings_beside_it():
    """The limit stands between the served path's largest reading and int8
    weights' smallest, with room on both sides. `int8_kv`, the control ISSUE
    52 asked for beside it, reads the served path's own level on the chip and
    so does NOT come out not correct: the file says so and gives the readings
    (PERF.md sections 2 and 7); what this holds is that the record stays
    honest about it."""
    check = config()["bench"]["check"]
    got = check["readings"]
    served, limit = got["served"], check["tolerance_rms_rel"]
    assert len(served) >= 6 and max(served) < limit
    weights, rows = got["int8_weights"], got["int8_kv"]
    assert len(weights) >= 2 and len(rows) >= 2 and limit < min(weights)
    assert limit / max(served) > 1.2 and min(weights) / limit > 1.1
    # the cache's rows in int8: inside the served path's range, not above the limit
    assert min(served) < min(rows) and max(rows) < limit
    assert "does NOT come out not correct" in check["why"]
    assert "PR 52" in check["readings_origin"]


ZERO = {
    "moe": {"layer_steps": 0.0, "assignments": 0.0, "experts_touched": 0.0, "max_expert_load": 0.0,
            "assignments_made": 0.0},
    "pool": {"decode_steps": 0, "lane_steps": 0, "window_rows": 0, "full_rows": 0, "lanes_past_window": 0,
             "window_blocks_past": 0, "window_in_use_steps": 0, "window_capacity_steps": 0,
             "full_in_use_steps": 0, "full_capacity_steps": 0, "window_blocks_given_back": 0},
}
# 100 decode steps of 50 lanes, 32 of them past the window at 14,000 tokens
ONE = {
    "moe": {"layer_steps": 400.0, "assignments": 400 * 25.0, "experts_touched": 400 * 17.0,
            "max_expert_load": 400 * 3.0, "assignments_made": 400 * 200.0},
    "pool": {"decode_steps": 100, "lane_steps": 5000, "window_rows": 100 * (32 * 4096 + 18 * 700),
             "full_rows": 100 * (32 * 14_000 + 18 * 700), "lanes_past_window": 3200,
             "window_blocks_past": 3200 * 258, "window_in_use_steps": 100 * 9000,
             "window_capacity_steps": 100 * 10_899, "full_in_use_steps": 100 * 29_000,
             "full_capacity_steps": 100 * 42_999, "window_blocks_given_back": 2000},
}


def made_up_ctx(a: dict, b: dict, ops=(), facts=None) -> dict:
    """A run's context as `run.py` builds it, with one device plane and one
    `decode_multi` execution of four steps."""
    plane = {
        "name": "/device:TPU:0", "span": [0.0, 4e9],
        "ops": [list(o) for o in ops],
        "modules": [["decode_multi", 1e6, 3.9e9, 0]],
    }
    return {
        "client": {"live": {"lanes": 50.0, "context": 9212.0}}, "clock": {},
        "ledger0": dict(a), "ledger1": dict(b), "ledger_t0": dict(a), "ledger_t1": dict(b),
        "prom0": "", "prom1": "", "config": config(), "mix": {}, "notes": {},
        "facts": {"decode_horizon": 4, "device_kind": "TPU v5 lite", "num_blocks": 43000,
                  "window_blocks": 10900, **(facts or {})},
        "trace": {"planes": [plane], "busy_s": 2.0, "window_s": 4.0},
    }


OPS = [
    # a window layer's paged call and the full layer's: told by the page array's block count
    ["%tpu_custom_call.3 = (bf16[64,8,6,128]{3,2,1,0}, bf16[8,10900,16,128]{3,2,1,0}, bf16[8,10900,16,128]{3,2,1,0}) custom-call(s32[64,257] %t, s32[64] %c, bf16[64,8,6,128] %q, bf16[8,10900,16,128] %k, bf16[8,10900,16,128] %v, bf16[64,1024] %kn, bf16[64,1024] %vn)", 2e6, 80e6],
    ["%tpu_custom_call.4 = (bf16[64,8,6,128]{3,2,1,0}, bf16[8,43000,16,128]{3,2,1,0}, bf16[8,43000,16,128]{3,2,1,0}) custom-call(s32[64,1536] %t, s32[64] %c, bf16[64,8,6,128] %q, bf16[8,43000,16,128] %k, bf16[8,43000,16,128] %v, bf16[64,1024] %kn, bf16[64,1024] %vn)", 9e7, 50e6],
    # a held experts' grouped product: a custom call that takes a stack
    ["%tpu_custom_call.7 = bf16[256,3072]{1,0} custom-call(s32[33] %g, bf16[256,3072] %x, bf16[32,3072,3072] %w)", 1.5e8, 120e6],
    # a device loop's wrapper is left out; a projection touches no page array
    ["%while.3 = (s32[], bf16[8,10900,16,128]{3,2,1,0}) while((s32[], bf16[8,10900,16,128]) %t)", 3e8, 9e6],
    ["%fusion.40 = bf16[64,6144]{1,0} fusion(bf16[64,3072] %h, bf16[3072,6144] %w)", 3.2e8, 5e6],
]


def test_readers_on_a_made_up_run():
    ctx = made_up_ctx(ZERO, ONE, OPS)
    d = dims()
    assert read(ctx, "window_attn_ms") == pytest.approx(80.0 / 4)
    assert read(ctx, "full_attn_ms") == pytest.approx(50.0 / 4)
    need = counts.rows_bytes(d, 32 * 4096 + 18 * 700 + 50, 4) / 819e9 * 1e3  # 2.9 ms
    assert read(ctx, "window_attn_roofline") == pytest.approx(100 * need / 20.0)
    need = counts.rows_bytes(d, 32 * 14_000 + 18 * 700 + 50, 1) / 819e9 * 1e3  # 2.3 ms
    assert read(ctx, "full_attn_roofline") == pytest.approx(100 * need / 12.5)
    assert ctx["notes"]["window_attn_roofline"]["rows_a_step"] == pytest.approx(32 * 4096 + 18 * 700)
    # a lane past the window holds what 4,096 positions come to, not its context's 875 blocks
    assert read(ctx, "window_pages_per_lane") == pytest.approx(258.0)
    share = 100 * (4 * 9000 + 29_000) / (4 * 10_899 + 42_999)
    assert read(ctx, "kv_pool_in_use_share") == pytest.approx(share)
    assert ctx["notes"]["kv_pool_in_use_share"]["window_blocks_given_back"] == 2000
    assert read(ctx, "afmoe_experts_ms") == pytest.approx(120.0 / 4)
    assert read(ctx, "afmoe_experts_touched") == pytest.approx(17.0)
    need = counts.experts_bytes(d, 17.0 * 4) / 819e9 * 1e3  # 4.7 ms
    assert read(ctx, "afmoe_experts_roofline") == pytest.approx(100 * need / 30.0)
    # the every-cell metrics read numbers here: the attention's custom calls
    # without the experts' (a grouped product is a custom call too), the
    # whole step's share and the kernel's own, neither over 100
    both = device_trace.read(ctx, metric_file("attn_kernel_ms")["params"])
    assert both == pytest.approx(130.0 / 4)
    mfu = device_trace.read(ctx, metric_file("decode_step_mfu")["params"])
    assert mfu is not None and 0 < mfu < 100
    c = counts.step_counts(d, 50.0, 9212.0)
    assert ctx["notes"]["decode_step_mfu"]["least_ms"] == pytest.approx(
        1e3 * counts.least_seconds(c, peaks_for("TPU v5 lite"))[0])
    assert not [k for k in ctx["notes"] if k.endswith("_error")], ctx["notes"]


def metric_file(name: str) -> dict:
    return manifest.load_json("cellbench", "metrics", name + ".json")


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers_give_nothing_where_there_is_nothing_to_read(name):
    """The parent's program cannot run this cell, but the driver lays these
    files over its checkout all the same, and the traced runs of every cell
    read them: a ledger without the `pool` group and a server that reports no
    `window_blocks` (every older program's), an untraced run, a trace in
    which the operand finds nothing give None and never an exception."""
    fusion = [["%fusion.1 = bf16[64,4096]{1,0} fusion(...)", 2e6, 5e6]]
    older = lambda x: {"moe": x["moe"]}
    parents = made_up_ctx(older(ZERO), older(ONE), OPS)
    del parents["facts"]["window_blocks"]
    for ctx in (
        made_up_ctx({}, {}, fusion),
        dict(made_up_ctx({}, {}), trace=None),
        dict(made_up_ctx({}, {}), ledger0=None, ledger1=None),
        made_up_ctx(ZERO, ZERO, fusion),
    ):
        assert read(ctx, name) is None
    if READERS[name] is page_pool:
        assert read(parents, name) is None
    # the counters alone give the counts and nothing that needs the trace
    got = read(dict(made_up_ctx(ZERO, ONE), trace=None), name)
    want = {"window_pages_per_lane": 258.0, "afmoe_experts_touched": 17.0,
            "kv_pool_in_use_share": 100 * (4 * 9000 + 29_000) / (4 * 10_899 + 42_999)}.get(name)
    assert (got == pytest.approx(want)) if want else got is None


def describe(cell: str) -> dict:
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "cellbench", "run.py"), "--workload", cell, "--describe"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_the_cell_resolves_and_describes():
    doc = describe(CELL)
    assert doc["config"] == CONFIG and doc["chips"] == 1
    assert doc["reference"] == "afmoe" and doc["counts"] == "afmoe_decode"
    assert doc["traffic"] == "long-short-steady" and doc["generator"] == "stratified_open_loop"
    judged = {k for k, v in doc["metrics"].items() if v["group"] == "end_to_end"}
    assert judged == {"tpot_p50_ms", "setup_s"}
    for name, reader in READERS.items():
        assert doc["metrics"][name]["reader"] == reader.__name__.rsplit(".", 1)[-1]
    bench = manifest.load_json("BENCHMARK.json")
    mine = 0
    for entry in bench["per_layer"]:
        listed = entry.get("workloads")
        assert (entry["name"] in doc["metrics"]) == (listed is None or CELL in listed), entry["name"]
        if entry["name"] in READERS:
            mine += 1
            assert listed == [CELL] and entry["moves"] == "tpot_p50_ms"
            m = metric_file(entry["name"])
            for key in ("unit", "better", "source", "layer", "moves"):
                assert m[key] == entry[key], (entry["name"], key)
    assert mine == len(READERS) == 9
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (CELL, CONFIG, "long-short-steady", 1)
    assert len(cell["why"]) <= 200 and len(bench["configs"][-1]["why"]) <= 200
    # the four-chip quota is untouched: every cell is one chip
    assert all(w["chips"] == 1 for w in bench["workloads"])
    # the declared check fits the full run: 2 + 14 x cells runs of run_seconds + 60, 180 s a cell, 1,200 spare
    n = len(bench["workloads"])
    assert (2 + 14 * n) * (bench["run_seconds"] + 60) + 180 * n + 1200 < 43_200


def test_every_cell_still_resolves_and_the_accepted_lists_are_prefixes():
    """Every accepted list of `BENCHMARK.json` is a prefix of the new one (the
    driver reads the lists by position; PR 52 appends one configuration, one
    cell and nine per-layer metrics and edits nothing), and every older cell
    resolves to what it resolved to: what
    `test_cellbench_ssm2_moe.py::test_the_cell_resolves_and_describes` holds
    beside its pin of the lists' ends (`tests/conftest.py`)."""
    bench = manifest.load_json("BENCHMARK.json")
    names = lambda key: [e["name"] for e in bench[key]]
    assert names("configs") == ACCEPTED["configs"] + [CONFIG]
    assert names("workloads") == ACCEPTED["workloads"] + [CELL]
    assert names("end_to_end") == ACCEPTED["end_to_end"]
    per_layer = names("per_layer")
    n = len(ACCEPTED["per_layer"])
    assert n == 56 and per_layer[:n] == ACCEPTED["per_layer"] and per_layer[n:] == list(READERS)
    # what test_cellbench_expert_products.py pins beside the list's length
    assert per_layer.index("decode_step_mfu") == 11 and per_layer[10] == "decode_device_ms"
    assert bench["per_layer"][11] == {"name": "decode_step_mfu", "unit": "%", "better": "higher",
                                     "source": "device_trace", "layer": "ModelRunner step", "moves": "tpot_p50_ms"}
    assert not [x for x in per_layer if "mfu" in x and x != "decode_step_mfu"]
    for w in bench["workloads"]:
        mine = {x["name"]: x for x in manifest.Cell(w["name"]).metrics("per_layer")}
        assert "decode_step_mfu" in mine, w["name"]
        for name, x in mine.items():
            if name.endswith("_roofline"):
                assert x["moves"] == mine["decode_step_mfu"]["moves"], name
    assert bench["run_seconds"] == 51 and bench["paths"] == ["cellbench", "tests/cellbench"]
    assert bench["command"] == ["python3", "cellbench/run.py"]
    for entry in bench["per_layer"][:n]:
        assert CELL not in (entry.get("workloads") or []), entry["name"]
    bounds = {e["name"]: e.get("bound") for e in bench["end_to_end"]}
    assert bounds == {"out_tok_s": 0.1, "tpot_p50_ms": 0.1, "setup_s": 0.1}
    nemotron = describe("nemotron3-super-bf16-l11-e128.plan-steady")
    assert nemotron["reference"] == "ssm2_moe" and nemotron["counts"] == "ssm2_moe_decode"
    assert nemotron["traffic"] == "plan-steady" and nemotron["chips"] == 1
    for name in ACCEPTED["per_layer"][-7:]:
        assert name in nemotron["metrics"], name
    assert not set(READERS) & set(nemotron["metrics"])
    every_cell = [e["name"] for e in bench["per_layer"] if "workloads" not in e]
    mine = describe(CELL)["metrics"]
    assert every_cell and all(name in mine for name in every_cell)


def test_the_reference_answers_the_checks_questions_with_both_controls():
    """`cellbench/refcheck.py`'s own loop on a toy of the same shape: the
    served path's number and each control's over the probes asked about, the
    controls reading worse than a served path that is the reference itself."""
    import numpy as np

    from cellbench import refcheck
    from tests.test_afmoe import HF

    d = ref.dims(HF)
    *layers, top = list(ref.seeded_layers(d, 0))
    rng = np.random.default_rng(3)
    probes = []
    for _ in range(2):
        tokens = [int(t) for t in rng.integers(3, HF["vocab_size"], 44)]
        rows = list(range(35, 43))
        logits = np.asarray(ref.forward(layers, top, d, [tokens], rows))[0]
        # the last block at the wanted rows alone is the whole pass's rows
        whole = np.asarray(ref.forward(layers, top, d, [tokens]))[0]
        np.testing.assert_allclose(logits, whole[rows], atol=2e-5)
        ids = np.argsort(-logits, axis=-1)[:, :10]
        lps = np.take_along_axis(logits, ids, axis=-1).astype("float32")
        lps = lps - np.log(np.exp(logits).sum(-1, keepdims=True))
        probes.append({"tokens": tokens, "rows": rows, "top_ids": ids.tolist(),
                       "top_lps": lps.astype("float64").tolist()})
    out = refcheck.answer(ref, layers, top, d, {"probes": probes, "lower": ["int8_weights", "int8_kv"]}, {})
    assert out["served"]["rms_rel"] < 1e-5 and out["served"]["positions"] == 16
    assert out["int8_weights"]["rms_rel"] > 1e-3 and out["int8_kv"]["rms_rel"] > 1e-3
