"""What PR 46 adds to the benchmark: the Mamba-2, latent-expert
configuration's file against the catalog, its counts against `param_count`
and against counts worked by hand, its mix, its seven per-layer metrics
through their readers (on a made-up run and on a saved slice of this PR's own
trace), its check's limit against the readings beside it, each control
against that limit, and what the cell resolves to."""

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
from cellbench.counts import ssm2_moe_decode as counts  # noqa: E402
from cellbench.generators import stratified_open_loop as gen  # noqa: E402
from cellbench.manifest import hf_config  # noqa: E402
from cellbench.peaks import peaks_for  # noqa: E402
from cellbench.readers import device_trace, expert_layers, held_share, ssm_layers  # noqa: E402
from cellbench.reference import ssm2_moe as ref  # noqa: E402

CELL = "nemotron3-super-bf16-l11-e128.plan-steady"
CONFIG = "nemotron3-super-bf16-l11-e128"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
READERS = {
    "held_experts_ms": expert_layers, "held_experts_roofline": expert_layers,
    "held_experts_touched": expert_layers, "held_assignment_share": held_share,
    "ssm2_step_ms": ssm_layers, "ssm2_step_roofline": ssm_layers,
    "ssm2_prefill_ms_per_ktok": ssm_layers,
}
REDUCED = [
    "bos_token_id", "eos_token_id", "hybrid_override_pattern", "max_position_embeddings",
    "n_routed_experts", "num_hidden_layers", "num_nextn_predict_layers",
]


def config() -> dict:
    return manifest.load_json("cellbench", "configs", CONFIG + ".json")


def catalog_row() -> dict:
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        return next(
            r for r in map(json.loads, f)
            if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
        )


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
    # the cut: the first 11 layers, one whole period of the published string
    assert doc["num_hidden_layers"] == 11
    assert doc["hybrid_override_pattern"] == row["config"]["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    assert doc["max_position_embeddings"] == 8192 and doc["num_nextn_predict_layers"] == 0
    # the held share: 128 of the router's 512 experts, the first of four chips'
    assert (doc["n_routed_experts"], doc["n_routed_experts_published"],
            doc["first_held_expert"], doc["expert_share_chips"]) == (128, 512, 0, 4)
    assert row["config"]["n_routed_experts"] == 512
    # no width, head count, experts per token or vocabulary is cut
    for key in ("hidden_size", "moe_intermediate_size", "moe_latent_size",
                "moe_shared_expert_intermediate_size", "mamba_num_heads", "mamba_head_dim",
                "ssm_state_size", "n_groups", "conv_kernel", "chunk_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "num_experts_per_tok", "vocab_size"):
        assert doc[key] == row["config"][key], key
    assert doc["tie_word_embeddings"] is False and doc["torch_dtype"] == "bfloat16"
    assert doc["bench"]["server"]["env"] == {"DYN_CHUNK_BUDGET": "512"}
    assert doc["bench"]["server"]["args"] == ["--context-length", "8192", "--max-batch", "64"]
    assert doc["bench"]["check"]["controls"] == ["int8_weights", "bf16_state"]
    said = " ".join(doc["bench"]["assumed"])
    for what in ("no rotary embedding", "float32", "1e-20", "e_score_correction_bias",
                 "init scales", "checkpoint names", "untried", "torch_dtype bfloat16"):
        assert what in said, what
    deployment = doc["bench"]["deployment"]
    for what in ("32 TPU v5e chips", "8 pipeline stages", "4 chips a stage", "128 a chip",
                 "chip 0 of stage 0", "not written", "88%"):
        assert what in deployment, what
    entry = next(c for c in manifest.load_json("BENCHMARK.json")["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"] and sorted(entry["reduced"]) == REDUCED
    probes = doc["bench"]["check"]["probes"]
    # short sequences through a packed prefill, and four whose prompts cross
    # two chunk boundaries before they decode through slot and pages: 512 of
    # the check's 576 positions lie past 1,100 tokens, where a state kept one
    # precision lower shows (section 2 of PERF.md)
    assert [(p["count"], p["prompt_tokens"], p["output_tokens"]) for p in probes] == [
        (2, 60, 32), (4, 1100, 128)]


def test_counts_against_param_count_whole_and_cut():
    """The counts' own sum of every parameter is the family's `param_count`:
    120,668,707,840 for the catalog's row without its prediction head,
    5,453,470,080 for the cut."""
    from dynamo_tpu.models import ssm2_moe

    whole = dict(catalog_row()["config"], num_nextn_predict_layers=0)
    d = ref.dims(whole)
    assert counts.param_count(d) == 120_668_707_840
    assert counts.param_count(d) == ssm2_moe.param_count(ssm2_moe.Ssm2MoeConfig.from_hf_dict(whole))
    cut = hf_config(config())
    d = ref.dims(cut)
    assert counts.param_count(d) == 5_453_470_080
    assert counts.param_count(d) == ssm2_moe.param_count(ssm2_moe.Ssm2MoeConfig.from_hf_dict(cut))
    # 2 x 5,453,470,080 bytes: over a quarter of the chip on weights alone
    assert 2 * counts.param_count(d) > 0.25 * peaks_for("TPU v5 lite")["hbm_bytes"]


def test_counts_against_hand_worked():
    d = ref.dims(hf_config(config()))
    assert (d["layers"], d["attn_layers"], counts.mamba_layers(d), counts.expert_layers(d)) == (11, 1, 5, 5)
    # what `readers/expert_layers.py` asks under: `layers - first_dense` expert layers
    assert d["layers"] - d["first_dense"] == 5
    assert (d["d_inner"], d["conv_dim"], d["ssm_heads"], d["ssm_head_dim"], d["d_state"], d["groups"]) == (
        8192, 10240, 128, 64, 128, 8)
    assert (d["experts"], d["router_experts"], d["first_held"], d["top_k"], d["chunk"]) == (128, 512, 0, 22, 128)
    # a Mamba-2 mixer: in 4096 x 18,560, the convolution's taps and bias,
    # three constants a head, the gated norm, out 8192 x 4096
    assert counts.mamba_mixer_params(d) == 76_021_760 + 40_960 + 10_240 + 384 + 8_192 + 33_554_432 == 109_635_968
    # an attention mixer: q and out 4096 x 4096, k and v 4096 x 256
    assert counts.attention_mixer_params(d) == 2 * 16_777_216 + 2 * 1_048_576 == 35_651_584
    # an expert layer beside its experts: router 4096 x 512 and its bias, two
    # latent projections 4096 x 1024, the shared expert 2 x 4096 x 5376
    assert counts.expert_layer_params(d) == 2_097_152 + 512 + 2 * 4_194_304 + 2 * 22_020_096 == 54_526_464
    assert counts.expert_params(d) == 2 * 1024 * 2688 == 5_505_024
    # a lane's slot: 5 layers x (4 MiB of state + 3 rows of 10,240 float32)
    assert counts.state_values(d) == 128 * 64 * 128 == 1_048_576 and d["tail_width"] == 30_720
    assert counts.state_bytes_per_lane(d) == 5 * (4_194_304 + 122_880) == 21_585_920
    assert counts.state_passes_a_step(4) == 1.25
    assert counts.scan_state_step_bytes(d, 48, 4) == 1.25 * 48 * 5 * 4_194_304
    assert counts.state_step_bytes(d, 48, 4) == 1.25 * 48 * 5 * 4_194_304 + 2 * 48 * 5 * 122_880
    # keys and values: 1 layer x 2 planes x 2 heads x 128 = 512 values, 1,024 bytes a token
    assert counts.kv_values_per_token(d) == 512
    # 48 tokens touch 112 of the 128 held experts of a layer under an even router
    assert counts.expected_experts_touched(d, 48) == pytest.approx(128 * (1 - (490 / 512) ** 48))
    assert 112.4 < counts.expected_experts_touched(d, 48) < 112.5
    assert 120.3 < counts.expected_experts_touched(d, 64) < 120.5
    assert counts.experts_bytes(d, 5 * 128) == 5 * 128 * 5_505_024 * 2 == 7_046_430_720
    lanes, ctx = 48, 800
    c = counts.step_counts(d, lanes, ctx)
    always = 5 * 109_635_968 + 35_651_584 + 5 * 54_526_464 + 4096 * 131_072
    assert always == 548_179_840 + 35_651_584 + 272_632_320 + 536_870_912 == 1_393_334_656
    touched = 5 * counts.expected_experts_touched(d, lanes)
    assert c["experts_touched"] == pytest.approx(touched)
    assert c["expert_bytes"] == pytest.approx(touched * 5_505_024 * 2)  # 6.19 GB
    assert c["weight_bytes"] == pytest.approx(2 * always + c["expert_bytes"])
    assert c["state_bytes"] == counts.state_step_bytes(d, lanes)
    assert c["kv_bytes"] == lanes * ctx * 1024 + lanes * 1024
    assert c["bytes"] == pytest.approx(
        c["weight_bytes"] + c["state_bytes"] + c["kv_bytes"] + lanes * 4096 * 2)
    per_token = always + 5 * 5.5 * 5_505_024  # 22 x 128 / 512 held experts a token
    attn_ops = 4 * lanes * 1 * 32 * 128 * ctx
    update_ops = 5 * lanes * 5 * 1_048_576
    conv_ops = 2 * lanes * 5 * 10_240 * 4
    assert c["ops"] == pytest.approx(2 * lanes * per_token + attn_ops + update_ops + conv_ops)
    least, bound = counts.least_seconds(c, peaks_for("TPU v5 lite"))
    # 8.98 GB of weights (6.19 of them held experts), 1.32 of slots, 0.04 of
    # rows at 819 GB/s: 12.6 ms; the operations need under a millisecond
    assert bound == "bytes" and 0.0125 < least < 0.0127
    assert 0.59 < c["expert_bytes"] / c["bytes"] < 0.61
    assert 0.12 < c["state_bytes"] / c["bytes"] < 0.14
    idle = counts.step_counts(d, 0, 0)
    assert idle["state_bytes"] == idle["kv_bytes"] == idle["expert_bytes"] == 0


def test_mix_is_what_the_issue_names():
    mix = manifest.Cell(CELL).mix
    assert mix["generator"] == "stratified_open_loop" and mix["temperature"] == 0.7
    # think-steady's prompts under reason-steady's outputs
    think = manifest.load_json("cellbench", "traffic", "think-steady.json")
    reason = manifest.load_json("cellbench", "traffic", "reason-steady.json")
    assist = manifest.load_json("cellbench", "traffic", "assist-steady.json")
    assert mix["prompt_tokens"] == think["prompt_tokens"] == {
        "dist": "lognormal", "median": 256, "sigma": 1.0, "min": 32, "max": 2048}
    assert mix["output_tokens"] == reason["output_tokens"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.5, "min": 128, "max": 1280}
    assert mix["pairing_seed"] == assist["pairing_seed"]
    assert mix["interarrival"] == {"dist": "exponential"} and mix["block_requests"] == 15
    assert "top_p" not in mix and "top_k" not in mix
    sets = gen.block_multisets(mix)
    assert min(sets["prompt_tokens"]) == 41 and max(sets["prompt_tokens"]) == 1602
    assert min(sets["output_tokens"]) == 205 and max(sets["output_tokens"]) == 1280
    # about 0.7 prompt tokens to an output token: decode does nearly all the work
    assert 0.6 < sum(sets["prompt_tokens"]) / sum(sets["output_tokens"]) < 0.8
    # warm-up phases as assist-steady has them: the check's two groups,
    # lingering streams of 400 to 720 tokens, the lone-lane program
    shape = lambda m: [sorted(k for k in p if k != "note") for p in m["warmup"]]
    assert shape(mix) == shape(assist)
    strip = lambda m: [{k: v for k, v in p.items() if k != "note"} for p in m["warmup"]]
    assert strip(mix) == strip(assist)
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
    assert set(readings) >= {"served", "int8_weights", "bf16_state"}
    served = readings["served"]
    assert len(served) >= 12
    # a tenth of room over the largest of them (17 seeds: 1.11), the mean
    # four of their standard deviations under the limit, and a tenth of room
    # under the smallest reading of either control (0.89)
    assert max(served) * 1.1 <= limit
    mean = sum(served) / len(served)
    assert mean + 4 * (sum((x - mean) ** 2 for x in served) / (len(served) - 1)) ** 0.5 <= limit
    for name in check["controls"]:
        assert len(readings[name]) >= 3
        assert limit <= 0.9 * min(readings[name]), name
    # the check as first drawn: the state's control read under some served
    # readings, so no limit stood between them; by length, the reference alone
    # shows why (the control at 1,132 tokens reads 1.5 times what it reads at 92)
    first = readings["first_probes_8x92_2x1132"]
    assert min(first["bf16_state"]) < max(first["served"])
    by_length = readings["controls_by_length_reference_alone"]
    short = [v for n, v in zip(by_length["length"], by_length["bf16_state"]) if n == 92]
    long = [v for n, v in zip(by_length["length"], by_length["bf16_state"]) if n == 1132]
    assert min(long) > 1.4 * max(short)
    for word in ("served", "int8", "bfloat16", "my chip run", "PR 46"):
        assert word in check["why"], word


def made_up_ctx(ledger0, ledger1, ops=(), annotations=(), modules=None):
    """A run as `run.py` hands it to a reader: the ledger at the window's
    edges and one device plane with one decode_multi execution of 4 steps."""
    plane = {
        "name": "/device:TPU:0", "span": (0.0, 2e9), "busy": [], "ops": list(ops),
        "modules": modules or [["decode_multi", 1e6, 8e8, 0]],
    }
    led = lambda extra: None if extra is None else {"steps_by_label": {}, **extra}
    return {
        "config": config(), "facts": {"device_kind": "TPU v5 lite", "decode_horizon": 4},
        "ledger0": led(ledger0), "ledger1": led(ledger1), "notes": {},
        "client": {"live": {"lanes": 48.0, "context": 800.0}},
        "trace": {"planes": [plane], "busy_s": 0.5, "window_s": 2.0},
        "annotations": list(annotations),
    }


def metric(name):
    return manifest.load_json("cellbench", "metrics", name + ".json")["params"]


def read(ctx, name):
    return READERS[name].read(ctx, metric(name))


ZERO = {
    "moe": {"layer_steps": 0.0, "assignments": 0.0, "experts_touched": 0.0, "max_expert_load": 0.0,
            "assignments_made": 0.0},
    "ssm": {"layer_steps": 0, "slots_live": 0, "slot_resets": 0, "scan_tokens": 0},
}
# 100 decode steps at 48 live lanes: 5 expert layers, 5 Mamba-2 layers; of the
# 22 assignments a token 5.4 fall on the 128 held experts
ONE = {
    "moe": {"layer_steps": 500.0, "assignments": 500 * 48 * 5.4, "experts_touched": 500 * 110.0,
            "max_expert_load": 500 * 8.0, "assignments_made": 500 * 48 * 22.0},
    "ssm": {"layer_steps": 500, "slots_live": 4800, "slot_resets": 7, "scan_tokens": 1024},
}


def test_readers_on_a_made_up_run():
    ops = [
        ["%ragged-dot.17 = bf16[1408,2688]{1,0} custom-call(s32[1] %n, s32[129] %g, bf16[1408,1024] %x, bf16[128,1024,2688] %w)", 2e6, 60e6],
        ["%ragged-dot.19 = bf16[1408,1024]{1,0} custom-call(s32[1] %n, s32[129] %g, bf16[1408,2688] %a, bf16[128,2688,1024] %w)", 7e7, 30e6],
        # the update of all 65 rows under the mask, and the product with C
        ["%fusion.31 = (f32[65,128,64,128]{3,2,1,0:T(8,128)}, f32[65,128,64]{2,1,0}) fusion(f32[65,128,64,128]{3,2,1,0} %s, f32[65,128,128] %b)", 1.1e8, 6e6],
        ["%fusion.33 = f32[65,128,64]{2,1,0} fusion(f32[65,128,64,128]{3,2,1,0} %s, f32[65,128,128] %c)", 1.2e8, 2e6],
        # a device loop's wrapper is left out; a projection touches no state
        ["%while.3 = (s32[], f32[65,128,64,128]{3,2,1,0}) while((s32[], f32[65,128,64,128]) %t)", 1.3e8, 9e6],
        ["%fusion.40 = bf16[64,18560]{1,0} fusion(bf16[64,4096] %h, bf16[4096,18560] %w)", 1.5e8, 5e6],
        ["%tpu_custom_call.9 = bf16[64,2,16,128]{3,2,1,0} custom-call(s32[64,512] %t)", 1.6e8, 2e6],
    ]
    ctx = made_up_ctx(ZERO, ONE, ops)
    assert read(ctx, "held_experts_ms") == pytest.approx(90.0 / 4)
    assert read(ctx, "held_experts_touched") == pytest.approx(110.0)
    assert read(ctx, "held_assignment_share") == pytest.approx(100 * 5.4 / 22)
    d = ref.dims(hf_config(config()))
    need = counts.experts_bytes(d, 110.0 * 5) / 819e9 * 1e3  # 7.4 ms
    assert read(ctx, "held_experts_roofline") == pytest.approx(100 * need / 22.5)
    assert 0 < read(ctx, "held_experts_roofline") < 100
    assert ctx["notes"]["held_experts_roofline"]["experts_touched_a_layer"] == pytest.approx(110.0)
    assert read(ctx, "ssm2_step_ms") == pytest.approx(8.0 / 4)  # 6 + 2 ms in 4 steps
    need = counts.scan_state_step_bytes(d, 48, 4) / 819e9 * 1e3  # 1.54 ms
    assert read(ctx, "ssm2_step_roofline") == pytest.approx(100 * need / 2.0)
    assert ctx["notes"]["ssm2_step_roofline"]["slots_live"] == pytest.approx(48.0)
    # the all-cells metrics find this family's counts under the same names
    # (the grouped products, custom calls too, are not counted among the kernel's)
    assert device_trace.read(ctx, metric("attn_kernel_ms")) == pytest.approx(2.0 / 4)
    roofline = device_trace.read(ctx, metric("decode_step_roofline"))
    assert roofline is not None and 0 < roofline < 100
    assert not [k for k in ctx["notes"] if k.endswith("_error")], ctx["notes"]
    # the chunked recurrence, in a mixed step that carried 512 prompt tokens
    chunk_ops = [
        ["%fusion.7 = f32[4,8,16,64,128]{4,3,2,1,0} fusion(f32[4,8,16,128,64] %xd, f32[4,8,128,128] %b)", 3e6, 1.5e6],
        ["%fusion.8 = f32[4,128,8,16,64]{4,3,2,1,0} fusion(f32[4,8,128,128] %cb, f32[4,8,16,128,64] %xd)", 6e6, 2.5e6],
        ["%fusion.9 = f32[4,8,128,128]{3,2,1,0} fusion(f32[512,8,128] %c, f32[512,8,128] %b)", 1e7, 1e6],
        # the decode half's update of the slots' array is not the chunk's
        ["%fusion.31 = f32[65,128,64,128]{3,2,1,0} fusion(f32[65,128,64,128] %s)", 2e7, 6e6],
        ["%fusion.40 = bf16[512,18560]{1,0} fusion(bf16[512,4096] %h, bf16[4096,18560] %w)", 3e7, 5e6],
    ]
    note = ["loop.dispatch", 1e6, 9e7, {"label": "mixed_step@c1", "prefill_tokens": 512}]
    ctx = made_up_ctx(ZERO, ONE, chunk_ops, [note], [["mixed_step", 2e6, 8e7, 0]])
    assert read(ctx, "ssm2_prefill_ms_per_ktok") == pytest.approx(1000 * 5.0 / 512)


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers_give_nothing_where_there_is_nothing_to_read(name):
    """The parent's program cannot run this cell, but the driver lays these
    files over its checkout all the same: a ledger without the counters (a
    `moe` group without `assignments_made` among them: every older program's),
    an untraced run, a trace in which the pattern finds nothing give None and
    never an exception."""
    fusion = [["%fusion.1 = bf16[64,4096]{1,0} fusion(...)", 2e6, 5e6]]
    older = {"moe": {k: v for k, v in ONE["moe"].items() if k != "assignments_made"}, "ssm": ONE["ssm"]}
    older0 = {"moe": {k: v for k, v in ZERO["moe"].items() if k != "assignments_made"}, "ssm": ZERO["ssm"]}
    for ctx in (
        made_up_ctx({}, {}, fusion),
        dict(made_up_ctx({}, {}), trace=None),
        dict(made_up_ctx({}, {}), ledger0=None, ledger1=None),
        made_up_ctx(ZERO, ZERO, fusion),
    ):
        assert read(ctx, name) is None
    assert held_share.read(made_up_ctx(older0, older, fusion), metric("held_assignment_share")) is None
    # the counters alone give the counts and nothing that needs the trace
    got = read(dict(made_up_ctx(ZERO, ONE), trace=None), name)
    want = {"held_experts_touched": 110.0, "held_assignment_share": 100 * 5.4 / 22}.get(name)
    assert (got == pytest.approx(want)) if want else got is None


def test_the_cell_resolves_and_describes():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "cellbench", "run.py"), "--workload", CELL, "--describe"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert doc["config"] == CONFIG and doc["chips"] == 1
    assert doc["reference"] == "ssm2_moe" and doc["counts"] == "ssm2_moe_decode"
    assert doc["traffic"] == "plan-steady" and doc["generator"] == "stratified_open_loop"
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
            m = manifest.load_json("cellbench", "metrics", entry["name"] + ".json")
            for key in ("unit", "better", "source", "layer", "moves"):
                assert m[key] == entry[key], (entry["name"], key)
    assert mine == len(READERS) == 7
    # appended at the end of each list: the driver reads the lists by position
    assert [e["name"] for e in bench["per_layer"]][-7:] == list(READERS)
    assert bench["workloads"][-1]["name"] == CELL and bench["configs"][-1]["name"] == CONFIG
    cell = bench["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "plan-steady", 1)
    assert len(cell["why"]) <= 200 and len(bench["configs"][-1]["why"]) <= 200
    assert len(bench["workloads"]) == 6 and len(bench["configs"]) == 6


def test_the_reference_answers_the_checks_questions_with_both_controls():
    """`cellbench/refcheck.py`'s own loop on a toy of the same shape: the
    served path's number and each control's over the probes asked about, the
    controls reading worse than a served path that is the reference itself
    rounded to bfloat16 logits."""
    import numpy as np

    from cellbench import refcheck
    from tests.test_ssm2_moe import HF

    d = ref.dims(HF)
    *layers, top = list(ref.seeded_layers(d, 0))
    rng = np.random.default_rng(3)
    probes = []
    for _ in range(3):
        tokens = [int(t) for t in rng.integers(3, HF["vocab_size"], 20)]
        rows = list(range(11, 19))
        logits = np.asarray(ref.forward(layers, top, d, [tokens], rows))[0]
        ids = np.argsort(-logits, axis=-1)[:, :10]
        lps = np.take_along_axis(logits, ids, axis=-1).astype("float32")
        lps = lps - np.log(np.exp(logits).sum(-1, keepdims=True))
        probes.append({"tokens": tokens, "rows": rows, "top_ids": ids.tolist(),
                       "top_lps": lps.astype("float64").tolist()})
    out = refcheck.answer(ref, layers, top, d, {"probes": probes, "lower": ["int8_weights", "bf16_state"]}, {})
    assert out["served"]["rms_rel"] < 1e-5 and out["served"]["positions"] == 24
    assert out["int8_weights"]["rms_rel"] > 1e-3 and out["bf16_state"]["rms_rel"] > 1e-3


def test_readers_on_a_saved_slice_of_this_prs_own_trace():
    """0.64 s of the device plane of a traced run of the final tree (my chip
    run, PR 46, call 7, seed 2147460103): four `decode_multi@H4B64` dispatches
    at 45 live lanes and a mixed step of one 512-token chunk, with their
    annotations. The numbers are what the readers read of it when it was
    saved; the ledger's counters are made to match the annotations (94.8 of
    128 held experts a layer and step and 24.46% of the assignments held, as
    the whole window's ledger read)."""
    path = os.path.join(DATA, "ssm2_moe_slice.nemotron3-super-bf16-l11-e128.plan-steady.json.gz")
    with gzip.open(path, "rt") as f:
        saved = json.load(f)
    notes = [a[3] for a in saved["annotations"]]
    assert [n["label"] for n in notes] == ["decode_multi@H4B64"] * 5 + ["mixed_step@c1"]
    assert [n["prefill_tokens"] for n in notes] == [0, 0, 0, 0, 0, 512]
    assert [n["state_slots"] for n in notes] == [45, 45, 45, 45, 45, 46]
    modules = [m[0] for m in saved["trace"]["planes"][0]["modules"]]
    assert modules.count("decode_multi") == 4 and "mixed_step" in modules
    steps = 4 * 4 + 1  # a horizon of four in each whole decode dispatch, one step in the mixed step
    live = 4 * 4 * 45 + 46
    moe = {"layer_steps": 5.0 * steps, "assignments": live * 5 * 22 * 0.2446,
           "experts_touched": 5 * steps * 94.8, "max_expert_load": 5 * steps * 6.0,
           "assignments_made": live * 5 * 22.0}
    ssm = {"layer_steps": 5 * steps, "slots_live": live, "slot_resets": 1, "scan_tokens": 512}
    led = lambda m, s: {"steps_by_label": {}, "moe": m, "ssm": s}
    ctx = {
        "config": config(), "facts": {"device_kind": "TPU v5 lite", "decode_horizon": 4},
        "notes": {}, "client": {"live": {"lanes": 45.0, "context": 760.0}},
        "trace": saved["trace"], "annotations": saved["annotations"],
        "ledger0": led(ZERO["moe"], ZERO["ssm"]), "ledger1": led(moe, ssm),
    }
    # 10 grouped products a step (two a layer, 5 layers): 17.7 ms of a 26.5 ms step
    assert read(ctx, "held_experts_ms") == pytest.approx(17.73, abs=0.02)
    assert read(ctx, "held_experts_touched") == pytest.approx(94.8)
    assert read(ctx, "held_assignment_share") == pytest.approx(24.46)
    # 94.8 x 5 experts of 11 MB: 5.22 GB, 6.37 ms at 819 GB/s
    assert read(ctx, "held_experts_roofline") == pytest.approx(35.9, abs=0.2)
    note = ctx["notes"]["held_experts_roofline"]
    assert note["expert_bytes_a_step"] == pytest.approx(94.8 * 5 * 5_505_024 * 2)
    # the state's operations: 5 layers' updates of all 65 rows and products
    # with C; 45 live slots' 4 MiB states at 1.25 passes need 1.44 ms
    assert read(ctx, "ssm2_step_ms") == pytest.approx(2.920, abs=0.01)
    assert read(ctx, "ssm2_step_roofline") == pytest.approx(49.4, abs=0.3)
    # the chunked recurrence of the one chunk's 512 prompt tokens: 1.6 ms
    assert read(ctx, "ssm2_prefill_ms_per_ktok") == pytest.approx(3.10, abs=0.05)
    # the all-cells metrics: the attention layer's paged call at 2 KV heads is
    # a Pallas custom call inside decode_multi; the grouped products, custom
    # calls too, are not counted among them
    attn = device_trace.read(ctx, metric("attn_kernel_ms"))
    assert attn == pytest.approx(0.241, abs=0.005)
    assert device_trace.read(ctx, metric("decode_device_ms")) == pytest.approx(26.46, abs=0.05)
    roofline = device_trace.read(ctx, metric("decode_step_roofline"))
    assert roofline == pytest.approx(46.7, abs=0.3) and ctx["notes"]["decode_step_roofline"]["bound"] == "bytes"
    assert not [k for k in ctx["notes"] if k.endswith("_error")], ctx["notes"]
    # no share passes 100%, and the step's time holds its parts
    assert attn + read(ctx, "held_experts_ms") + read(ctx, "ssm2_step_ms") < 26.46
