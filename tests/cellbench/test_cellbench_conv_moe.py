"""What PR 44 adds to the benchmark: the short-convolution, sparse-expert
configuration's file against the catalog, its counts against `param_count`
and against counts worked by hand, its mix, its five per-layer metrics
through the readers that were there (on a made-up run and on a saved slice of
this PR's own trace), its check's limit against the readings beside it, and
what the cell resolves to."""

from __future__ import annotations

import gzip
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from cellbench import manifest  # noqa: E402
from cellbench.counts import conv_moe_decode as counts  # noqa: E402
from cellbench.generators import stratified_open_loop as gen  # noqa: E402
from cellbench.manifest import hf_config  # noqa: E402
from cellbench.peaks import peaks_for  # noqa: E402
from cellbench.readers import device_trace, expert_layers, ssm_layers  # noqa: E402
from cellbench.reference import conv_moe as ref  # noqa: E402

CELL = "lfm2-8b-a1b-bf16-l16.assist-steady"
CONFIG = "lfm2-8b-a1b-bf16-l16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
READERS = {
    "routed_ffn_ms": expert_layers, "routed_ffn_roofline": expert_layers,
    "routed_experts_touched": expert_layers, "short_conv_ms": ssm_layers,
    "conv_slots_live": ssm_layers,
}
REDUCED = ["bos_token_id", "eos_token_id", "layer_types", "max_position_embeddings", "num_hidden_layers"]


def config() -> dict:
    return manifest.load_json("cellbench", "configs", CONFIG + ".json")


def catalog_row() -> dict:
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        return next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")


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
    # the cut: the first 16 layers, four whole periods of the published list
    assert doc["num_hidden_layers"] == 16
    assert doc["layer_types"] == row["config"]["layer_types"][:16] == ["conv", "conv", "full_attention", "conv"] * 4
    assert doc["max_position_embeddings"] == 8192
    # no width, head count, expert count, experts per token or vocabulary is cut
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads",
                "num_key_value_heads", "num_experts", "num_experts_per_tok", "vocab_size", "conv_L_cache"):
        assert doc[key] == row["config"][key], key
    assert doc["tie_word_embeddings"] is True and doc["torch_dtype"] == "bfloat16"
    assert doc["bench"]["server"]["env"] == {"DYN_CHUNK_BUDGET": "512"}
    assert doc["bench"]["server"]["args"] == ["--context-length", "8192", "--max-batch", "64"]
    assert doc["bench"]["check"]["controls"] == ["int8_weights", "fp8_conv"]
    said = " ".join(doc["bench"]["assumed"])
    for what in ("tie_word_embeddings", "head_dim 64", "torch_dtype bfloat16", "expert_bias", "checkpoint names", "untried"):
        assert what in said, what
    assert "two pipeline stages" in doc["bench"]["deployment"]
    entry = next(c for c in manifest.load_json("BENCHMARK.json")["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"] and sorted(entry["reduced"]) == REDUCED
    probes = doc["bench"]["check"]["probes"]
    # short sequences through a packed prefill, and two whose prompts cross
    # two chunk boundaries before they decode through slot and pages
    assert [(p["count"], p["prompt_tokens"], p["output_tokens"]) for p in probes] == [
        (8, 60, 32), (2, 1100, 32)]


def test_counts_against_param_count_whole_and_cut():
    """The counts' own sum of every parameter is the family's `param_count`:
    8,339,930,560 for the catalog's row, 5,399,129,024 for the cut."""
    from dynamo_tpu.models import conv_moe

    whole = catalog_row()["config"]
    d = ref.dims(whole)
    assert counts.param_count(d) == 8_339_930_560
    assert counts.param_count(d) == conv_moe.param_count(conv_moe.ConvMoeConfig.from_hf_dict(whole))
    cut = hf_config(config())
    d = ref.dims(cut)
    assert counts.param_count(d) == 5_399_129_024
    assert counts.param_count(d) == conv_moe.param_count(conv_moe.ConvMoeConfig.from_hf_dict(cut))
    # 2 x 5,399,129,024 bytes: over a quarter of the chip on weights alone
    assert 2 * counts.param_count(d) > 0.25 * peaks_for("TPU v5 lite")["hbm_bytes"]


def test_counts_against_hand_worked():
    d = ref.dims(hf_config(config()))
    assert (d["layers"], d["attn_layers"], counts.conv_layers(d), counts.expert_layers(d)) == (16, 4, 12, 14)
    assert counts.mamba_layers(d) == 12  # the name `readers/ssm_layers.py` asks under
    # a convolution mixer: in 2048 x 6144, out 2048 x 2048, three taps of 2048
    assert counts.conv_mixer_params(d) == 12_582_912 + 4_194_304 + 6_144 == 16_783_360
    # an attention mixer: q and out 2048 x 2048, k and v 2048 x 512, two norms of 64
    assert counts.attention_mixer_params(d) == 2 * 4_194_304 + 2 * 1_048_576 + 128 == 10_485_888
    assert counts.expert_params(d) == 3 * 2048 * 1792 == 11_010_048
    # a lane's slot: 12 layers x 2 rows x 2048 bfloat16 = 98 KB whatever its
    # length; read and written every step (the tail shifts by one input)
    assert d["tail_width"] == 4096
    assert counts.tail_bytes_per_lane(d) == 12 * 4096 * 2 == 98_304
    assert counts.tail_step_bytes(d, 45) == 2 * 45 * 98_304
    # keys and values at the published bytes, however the cache lays them out:
    # 4 layers x 2 planes x 8 heads x 64 = 4,096 values, 8,192 bytes a token
    assert counts.kv_values_per_token(d) == 4096
    # 30 tokens touch all but a fraction of an expert of a layer's 32
    assert counts.expected_experts_touched(d, 30) == pytest.approx(32 * (1 - 0.875 ** 30))
    assert 31.4 < counts.expected_experts_touched(d, 30) < 31.5
    assert 13.2 < counts.expected_experts_touched(d, 4) < 13.3
    assert counts.experts_bytes(d, 14 * 32) == 14 * 32 * 11_010_048 * 2 == 9_865_003_008
    lanes, ctx = 45, 1100
    c = counts.step_counts(d, lanes, ctx)
    always = 12 * 16_783_360 + 4 * 10_485_888 + 2 * 3 * 2048 * 7168 + 14 * 2048 * 32 + 2048 * 65536
    assert always == 201_400_320 + 41_943_552 + 88_080_384 + 917_504 + 134_217_728 == 466_559_488
    touched = 14 * counts.expected_experts_touched(d, lanes)
    assert c["experts_touched"] == pytest.approx(touched) and touched > 14 * 31.9
    assert c["weight_bytes"] == pytest.approx(2 * always + touched * 11_010_048 * 2)
    assert c["mixer_bytes"] == 2 * (12 * 16_783_360 + 4 * 10_485_888)
    assert c["tail_bytes"] == 2 * lanes * 98_304
    assert c["kv_bytes"] == lanes * ctx * 8192 + lanes * 8192  # 0.41 GB
    assert c["bytes"] == pytest.approx(
        c["weight_bytes"] + c["tail_bytes"] + c["kv_bytes"] + lanes * 2048 * 2)
    attn_ops = 4 * lanes * 4 * 32 * 64 * ctx
    conv_ops = lanes * 12 * 2048 * 7
    per_token = always + 14 * 4 * 11_010_048
    assert c["ops"] == pytest.approx(2 * lanes * per_token + attn_ops + conv_ops)
    least, bound = counts.least_seconds(c, peaks_for("TPU v5 lite"))
    # 10.77 GB of weights, 0.41 of rows, 0.009 of tails at 819 GB/s: 13.7 ms;
    # the operations need 0.5 ms. The experts are nine tenths of the bytes
    assert bound == "bytes" and 0.0136 < least < 0.0138
    assert 0.87 < c["expert_bytes"] / c["bytes"] < 0.89
    idle = counts.step_counts(d, 0, 0)
    assert idle["tail_bytes"] == idle["kv_bytes"] == idle["expert_bytes"] == 0


def test_mix_is_what_the_issue_names():
    mix = manifest.Cell(CELL).mix
    assert mix["generator"] == "stratified_open_loop" and mix["temperature"] == 0.7
    # reason-steady's lengths: two sparse-expert models under one traffic
    reason = manifest.load_json("cellbench", "traffic", "reason-steady.json")
    assert mix["prompt_tokens"] == reason["prompt_tokens"] == {
        "dist": "lognormal", "median": 512, "sigma": 1.0, "min": 32, "max": 4096}
    assert mix["output_tokens"] == reason["output_tokens"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.5, "min": 128, "max": 1280}
    assert mix["pairing_seed"] == reason["pairing_seed"]
    assert mix["interarrival"] == {"dist": "exponential"} and mix["block_requests"] == 15
    assert "top_p" not in mix and "top_k" not in mix
    sets = gen.block_multisets(mix)
    assert min(sets["prompt_tokens"]) == 82 and max(sets["prompt_tokens"]) == 3204
    assert min(sets["output_tokens"]) == 205 and max(sets["output_tokens"]) == 1280
    # about 1.4 prompt tokens to an output token
    assert 1.3 < sum(sets["prompt_tokens"]) / sum(sets["output_tokens"]) < 1.5
    # warm-up phases as think-steady has them: the check's two groups,
    # lingering streams of 400 to 720 tokens, the lone-lane program
    think = manifest.load_json("cellbench", "traffic", "think-steady.json")
    shape = lambda m: [sorted(k for k in p if k != "note") for p in m["warmup"]]
    assert shape(mix) == shape(think)
    assert [p.get("check_group") for p in mix["warmup"] if "check_group" in p] == [0, 1]
    lingering = [r["output_tokens"] for p in mix["warmup"] if p.get("linger") for r in p["requests"]]
    assert min(lingering) == 400 and max(lingering) == 720
    # the rate is a whole number of blocks of 15 in the 51 s window, the ramp
    # whole blocks too and at least the longest stream's duration
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


def test_the_limit_lies_between_the_readings_beside_it():
    """The configuration's file gives the check's limit with the readings it
    was set from: every served reading passes it, every reading of each
    control fails it, with room on both sides."""
    check = config()["bench"]["check"]
    limit, readings = check["tolerance_rms_rel"], check["readings"]
    assert set(readings) == {
        "served", "int8_weights", "fp8_conv", "short_sequences_only", "routing_fixed"}
    assert len(readings["served"]) >= 11
    # a tenth of room over the largest of them (27 seeds: 1.13), and the mean
    # four of their standard deviations under the limit
    served = readings["served"]
    assert max(served) * 1.1 <= limit
    mean = sum(served) / len(served)
    assert mean + 4 * (sum((x - mean) ** 2 for x in served) / (len(served) - 1)) ** 0.5 <= limit
    for name in check["controls"]:
        assert len(readings[name]) >= 2
        assert limit <= 0.9 * min(readings[name]), name
        # over the short sequences alone the controls read the same
        assert limit <= 0.9 * min(readings["short_sequences_only"][name]), name
    # with the choice of experts taken out of the comparison the served path
    # reads a fifth of what it reads with it, and each control well over that:
    # what the limit has to allow for is flipped choices, not arithmetic
    fixed = readings["routing_fixed"]
    assert max(fixed["served"]) < 0.25 * min(readings["served"])
    for name in check["controls"]:
        assert min(fixed[name]) > 2 * max(fixed["served"]), name
    for word in ("served", "int8", "8-bit", "my chip run", "PR 44"):
        assert word in check["why"], word


def made_up_ctx(ledger0, ledger1, ops=()):
    """A run as `run.py` hands it to a reader: the ledger at the window's
    edges and one device plane with one decode_multi execution of 4 steps."""
    plane = {
        "name": "/device:TPU:0", "span": (0.0, 2e9), "busy": [], "ops": list(ops),
        "modules": [["decode_multi", 1e6, 8e8, 0]],
    }
    led = lambda extra: None if extra is None else {"steps_by_label": {}, **extra}
    return {
        "config": config(), "facts": {"device_kind": "TPU v5 lite", "decode_horizon": 4},
        "ledger0": led(ledger0), "ledger1": led(ledger1), "notes": {},
        "client": {"live": {"lanes": 45.0, "context": 1100.0}},
        "trace": {"planes": [plane], "busy_s": 0.5, "window_s": 2.0},
        "annotations": [],
    }


def metric(name):
    return manifest.load_json("cellbench", "metrics", name + ".json")["params"]


def read(ctx, name):
    return READERS[name].read(ctx, metric(name))


ZERO = {
    "moe": {"layer_steps": 0.0, "assignments": 0.0, "experts_touched": 0.0, "max_expert_load": 0.0},
    "ssm": {"layer_steps": 0, "slots_live": 0, "slot_resets": 0, "scan_tokens": 0},
}
# 100 decode steps at 45 live lanes: 14 expert layers, 12 convolution layers
ONE = {
    "moe": {"layer_steps": 1400.0, "assignments": 1400 * 180.0, "experts_touched": 1400 * 31.9,
            "max_expert_load": 1400 * 11.0},
    "ssm": {"layer_steps": 1200, "slots_live": 4500, "slot_resets": 7, "scan_tokens": 5000},
}


def test_readers_on_a_made_up_run():
    ops = [
        ["%ragged-dot.17 = bf16[256,1792]{1,0} ragged-dot(bf16[256,2048] %x, bf16[32,2048,1792] %w, s32[32] %g)", 2e6, 60e6],
        ["%ragged-dot.19 = bf16[256,2048]{1,0} ragged-dot(bf16[256,1792] %a, bf16[32,1792,2048] %w, s32[32] %g)", 7e7, 30e6],
        # the convolution over the tail and the new input; the shifted tail
        ["%fusion.31 = (f32[65,2048]{1,0}, bf16[65,4096]{1,0:T(8,128)(2,1)}) fusion(bf16[65,4096]{1,0} %tail, f32[65,2048] %g)", 1.1e8, 3e6],
        ["%copy-done.2 = bf16[65,4096]{1,0:S(1)} copy-done((bf16[65,4096]{1,0}, u32[]) %c)", 1.2e8, 1e6],
        # a device loop's wrapper is left out; a projection touches no tail
        ["%while.3 = (s32[], bf16[65,4096]{1,0}) while((s32[], bf16[65,4096]) %t)", 1.3e8, 9e6],
        ["%fusion.40 = bf16[64,6144]{1,0} fusion(bf16[64,2048] %h, bf16[2048,6144] %w)", 1.5e8, 5e6],
        ["%tpu_custom_call.9 = bf16[64,4,8,128]{3,2,1,0} custom-call(s32[64,512] %t)", 1.6e8, 2e6],
    ]
    ctx = made_up_ctx(ZERO, ONE, ops)
    assert read(ctx, "routed_ffn_ms") == pytest.approx(90.0 / 4)
    assert read(ctx, "routed_experts_touched") == pytest.approx(31.9)
    d = ref.dims(hf_config(config()))
    need = counts.experts_bytes(d, 31.9 * 14) / 819e9 * 1e3  # 12.0 ms
    assert read(ctx, "routed_ffn_roofline") == pytest.approx(100 * need / 22.5)
    assert 0 < read(ctx, "routed_ffn_roofline") < 100
    assert ctx["notes"]["routed_ffn_roofline"]["experts_touched_a_layer"] == pytest.approx(31.9)
    assert read(ctx, "short_conv_ms") == pytest.approx(4.0 / 4)  # 3 + 1 ms in 4 steps
    assert read(ctx, "conv_slots_live") == pytest.approx(45.0)
    # the all-cells metrics find this family's counts under the same names
    assert device_trace.read(ctx, metric("attn_kernel_ms")) == pytest.approx(2.0 / 4)
    roofline = device_trace.read(ctx, metric("decode_step_roofline"))
    assert roofline is not None and 0 < roofline < 100
    assert "expert_layers_error" not in ctx["notes"] and "ssm_layers_error" not in ctx["notes"]


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers_give_nothing_where_there_is_nothing_to_read(name):
    """The parent's program cannot run this cell, but the driver lays these
    files over its checkout all the same: a ledger without the counters, an
    untraced run, a trace in which the pattern finds nothing give None and
    never an exception."""
    fusion = [["%fusion.1 = bf16[64,2048]{1,0} fusion(...)", 2e6, 5e6]]
    for ctx in (
        made_up_ctx({}, {}, fusion),
        dict(made_up_ctx({}, {}), trace=None),
        dict(made_up_ctx({}, {}), ledger0=None, ledger1=None),
        made_up_ctx(ZERO, ZERO, fusion),
    ):
        assert read(ctx, name) is None
    # the counters alone give the counts and nothing that needs the trace
    got = read(dict(made_up_ctx(ZERO, ONE), trace=None), name)
    want = {"routed_experts_touched": 31.9, "conv_slots_live": 45.0}.get(name)
    assert (got == pytest.approx(want)) if want else got is None


def test_the_cell_resolves_and_describes():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "cellbench", "run.py"), "--workload", CELL, "--describe"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert doc["config"] == CONFIG and doc["chips"] == 1
    assert doc["reference"] == "conv_moe" and doc["counts"] == "conv_moe_decode"
    assert doc["traffic"] == "assist-steady" and doc["generator"] == "stratified_open_loop"
    judged = {k for k, v in doc["metrics"].items() if v["group"] == "end_to_end"}
    assert judged == {"tpot_p50_ms", "setup_s"}
    for name, reader in READERS.items():
        assert doc["metrics"][name]["reader"] == reader.__name__.rsplit(".", 1)[-1]
    bench = manifest.load_json("BENCHMARK.json")
    for entry in bench["per_layer"]:
        if "workloads" not in entry and entry["moves"] in ("tpot_p50_ms", "setup_s"):
            assert entry["name"] in doc["metrics"], entry["name"]
        if entry["name"] in READERS:
            assert entry["workloads"] == [CELL] and entry["moves"] == "tpot_p50_ms"
    # the issue's five entries are declared once each; where they stand in the
    # list says nothing to the harness (`manifest` finds cells and metrics by name)
    names = [e["name"] for e in bench["per_layer"]]
    assert all(names.count(name) == 1 for name in READERS) and len(READERS) == 5
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "assist-steady", 1)
    assert len(cell["why"]) <= 200


# Two tests of earlier PRs hold their own entries to be the LAST of
# `BENCHMARK.json`'s lists (PR 38's cell, PR 40's eight metrics). A PR that adds
# a cell may only append to those lists (the driver reads them by position), so
# both are expected failures since this PR (`tests/conftest.py`
# `_PINS_WHAT_WENT`), and every other assertion of theirs is held below, for
# every cell and with no entry held to a place, so that the next cell's PR
# finds nothing here to work around.

EARLIER = {
    "mistral7b-int8.chat-steady": ("mistral7b-int8", "dense_gqa", "dense_gqa_decode", "chat-steady"),
    "qwen25-7b-int8.chat-sat": ("qwen25-7b-int8", "dense_gqa", "dense_gqa_decode", "chat-sat"),
    "joyai-flash-bf16-l5.reason-steady": ("joyai-flash-bf16-l5", "mla_moe", "mla_moe_decode", "reason-steady"),
    "jamba2-3b-bf16.think-steady": ("jamba2-3b-bf16", "hybrid_ssm", "hybrid_ssm_decode", "think-steady"),
}


@pytest.mark.parametrize("cell", sorted(EARLIER))
def test_every_cell_still_resolves_and_describes(cell):
    """The cells that were there resolve to what they resolved to, the new
    cell beside them; the metrics that list another cell are not this one's
    and the reverse."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "cellbench", "run.py"), "--workload", cell, "--describe"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    config_name, reference, counts_name, traffic = EARLIER[cell]
    assert (doc["config"], doc["reference"], doc["counts"], doc["traffic"], doc["chips"]) == (
        config_name, reference, counts_name, traffic, 1)
    assert doc["generator"] == "stratified_open_loop"
    judged = {k for k, v in doc["metrics"].items() if v["group"] == "end_to_end"}
    assert judged == {"tpot_p50_ms", "setup_s"} | ({"out_tok_s"} if cell.endswith("chat-sat") else set())
    assert not set(READERS) & set(doc["metrics"])
    bench = manifest.load_json("BENCHMARK.json")
    assert {w["name"] for w in bench["workloads"]} >= set(EARLIER) | {CELL}
    for entry in bench["per_layer"]:
        listed = entry.get("workloads")
        assert (entry["name"] in doc["metrics"]) == (listed is None or cell in listed), entry["name"]
    if cell == "jamba2-3b-bf16.think-steady":
        for name in ("ssm_step_ms", "ssm_step_roofline", "state_slots_live", "prefill_scan_ms_per_ktok"):
            assert doc["metrics"][name]["reader"] == "ssm_layers"
            assert next(e for e in bench["per_layer"] if e["name"] == name)["workloads"] == [cell]
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])


def test_the_launch_metrics_are_every_cells():
    """PR 40's eight metrics list no cells, so they are the new cell's too,
    declared as their files say."""
    from cellbench.readers import launch_split as ls

    launch = [
        "idle_hop_share", "idle_upload_share", "idle_enqueue_share", "idle_fetch_lead_share",
        "idle_fetch_drain_share", "launch_upload_ms", "launch_enqueue_ms", "upload_arrays_per_dispatch",
    ]
    bench = manifest.load_json("BENCHMARK.json")
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in launch:
        m = manifest.load_json("cellbench", "metrics", name + ".json")
        entry = declared[name]
        assert "workloads" not in entry
        for key in ("unit", "better", "source", "layer", "moves"):
            assert m[key] == entry[key], (name, key)
        assert entry["layer"] == "ModelRunner step" and entry["moves"] == "tpot_p50_ms"
        kind = m["params"]["kind"]
        assert manifest.reader(m["reader"]) is ls and kind in ("idle_part", "ledger_ratio", "phase_ms_per_call")
        assert kind != "idle_part" or m["params"]["part"] in ls.PARTS
        assert kind != "phase_ms_per_call" or m["params"]["phase"] in ls.CHILDREN.values()
    for w in bench["workloads"]:
        mine = {m["name"] for m in manifest.Cell(w["name"]).metrics("per_layer")}
        assert set(launch) <= mine, w["name"]


def test_readers_on_a_saved_slice_of_this_prs_own_trace():
    """0.46 s of the device plane of the cell's cold traced run (my chip run,
    PR 44, call A, seed 2147440001): two `decode_multi@H4B64` dispatches at
    34 and 35 live lanes with two mixed steps of one chunk (512 and 94 prompt
    tokens) between them, and their annotations. The numbers are what the
    readers read of it when it was saved; the ledger's counters are made to
    match the annotations (31.8 of 32 experts a layer and step, as the whole
    window's ledger read)."""
    path = os.path.join(DATA, "conv_moe_slice.lfm2-8b-a1b-bf16-l16.assist-steady.json.gz")
    with gzip.open(path, "rt") as f:
        saved = json.load(f)
    notes = [a[3] for a in saved["annotations"]]
    assert [n["label"] for n in notes] == (
        ["decode_multi@H4B64"] * 2 + ["mixed_step@c1"] * 2 + ["decode_multi@H4B64"])
    assert [n["state_slots"] for n in notes] == [35, 34, 35, 35, 35]
    assert [n["prefill_tokens"] for n in notes] == [0, 0, 512, 94, 0]
    modules = [m[0] for m in saved["trace"]["planes"][0]["modules"]]
    assert modules.count("decode_multi") == 2 and "mixed_step" in modules
    steps = 2 * 4 + 2  # a horizon of four in each whole decode dispatch, one step a mixed step
    live = 4 * (34 + 35) + 35 + 35
    moe = {"layer_steps": 14.0 * steps, "assignments": 14.0 * live * 4,
           "experts_touched": 14 * steps * 31.8, "max_expert_load": 14 * steps * 9.0}
    ssm = {"layer_steps": 12 * steps, "slots_live": live, "slot_resets": 1, "scan_tokens": 606}
    led = lambda m, s: {"steps_by_label": {}, "moe": m, "ssm": s}
    ctx = {
        "config": config(), "facts": {"device_kind": "TPU v5 lite", "decode_horizon": 4},
        "notes": {}, "client": {"live": {"lanes": 34.5, "context": 1180.0}},
        "trace": saved["trace"], "annotations": saved["annotations"],
        "ledger0": led(ZERO["moe"], ZERO["ssm"]), "ledger1": led(moe, ssm),
    }
    # 42 grouped products a step (three a layer, 14 layers): 25 ms of a 28 ms step
    assert read(ctx, "routed_ffn_ms") == pytest.approx(24.99, abs=0.02)
    assert read(ctx, "routed_experts_touched") == pytest.approx(31.8)
    # 31.8 x 14 experts of 22 MB: 9.80 GB, 11.97 ms at 819 GB/s
    assert read(ctx, "routed_ffn_roofline") == pytest.approx(47.9, abs=0.2)
    note = ctx["notes"]["routed_ffn_roofline"]
    assert note["expert_bytes_a_step"] == pytest.approx(31.8 * 14 * 11_010_048 * 2)
    # the tails' operations: 12 layers' convolutions and shifts and the waits
    # on the compiler's copies of the `[65, 4096]` arrays, 73 us a step
    assert read(ctx, "short_conv_ms") == pytest.approx(0.0733, abs=0.002)
    assert read(ctx, "conv_slots_live") == pytest.approx(live / steps)  # 34.6
    # the all-cells metrics: the four attention layers' paged calls at 64-wide
    # heads in pairs are Pallas custom calls inside decode_multi; the grouped
    # products, custom calls too, are not counted among them
    attn = device_trace.read(ctx, metric("attn_kernel_ms"))
    assert attn == pytest.approx(1.134, abs=0.01)
    assert device_trace.read(ctx, metric("decode_device_ms")) == pytest.approx(28.11, abs=0.05)
    roofline = device_trace.read(ctx, metric("decode_step_roofline"))
    assert roofline == pytest.approx(47.96, abs=0.2) and ctx["notes"]["decode_step_roofline"]["bound"] == "bytes"
    assert "expert_layers_error" not in ctx["notes"] and "ssm_layers_error" not in ctx["notes"]
    # no share passes 100%, and the step's time is its parts: 25.0 of grouped
    # products, 1.1 of attention, 0.07 of tails in 28.1
    assert attn + read(ctx, "routed_ffn_ms") + read(ctx, "short_conv_ms") < 28.11
