"""What PR 38 adds to the benchmark: the hybrid state-space configuration's
file against the catalog, its counts against counts worked by hand, its mix,
the reader of its per-layer metrics on a made-up run and on a saved slice of
this PR's own trace, and what the cell resolves to."""

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
from cellbench.counts import hybrid_ssm_decode as counts  # noqa: E402
from cellbench.generators import stratified_open_loop as gen  # noqa: E402
from cellbench.manifest import hf_config  # noqa: E402
from cellbench.peaks import peaks_for  # noqa: E402
from cellbench.readers import ssm_layers  # noqa: E402
from cellbench.reference import hybrid_ssm as ref  # noqa: E402

CELL = "jamba2-3b-bf16.think-steady"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW_METRICS = ["ssm_step_ms", "ssm_step_roofline", "state_slots_live", "prefill_scan_ms_per_ktok"]


def config() -> dict:
    return manifest.load_json("cellbench", "configs", "jamba2-3b-bf16.json")


def test_configuration_file_holds_the_catalogs_keys_but_the_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "AI21-Jamba2-3B")
    doc = config()
    reduced = doc["bench"]["reduced"]
    assert doc["bench"]["source"] == row["source_url"]
    assert sorted(reduced) == ["bos_token_id", "eos_token_id", "max_position_embeddings"]
    for key, value in row["config"].items():
        if key in reduced:
            assert doc[key] != value, key
        else:
            assert doc[key] == value, key
    assert doc["num_hidden_layers"] == 28 and doc["vocab_size"] == 65536
    assert doc["max_position_embeddings"] == 8192
    # one variable, for the cold start's sake; the lease's TTL is the default's
    assert doc["bench"]["server"]["env"] == {"DYN_CHUNK_BUDGET": "512"}
    assert doc["bench"]["server"]["args"] == ["--context-length", "8192", "--max-batch", "64"]
    assert set(doc["bench"]["check"]["controls"]) == {"int8_weights", "bf16_state"}
    assert len(doc["bench"]["assumed"]) == 4
    entry = next(c for c in manifest.load_json("BENCHMARK.json")["configs"] if c["name"] == "jamba2-3b-bf16")
    assert entry["source"] == row["source_url"] and sorted(entry["reduced"]) == sorted(reduced)
    probes = doc["bench"]["check"]["probes"]
    # most of the compared positions lie past 1,100 tokens, where a state
    # kept in bfloat16 shows in the logits (the configuration's check.why)
    assert [(p["count"], p["prompt_tokens"], p["output_tokens"]) for p in probes] == [
        (3, 60, 8), (2, 1100, 96)]


def test_counts_against_hand_worked():
    d = ref.dims(hf_config(config()))
    assert (d["layers"], d["attn_layers"], counts.mamba_layers(d)) == (28, 2, 26)
    # a Mamba mixer: in 2560 x 10240, conv 4 x 5120 + 5120, x 5120 x 192 and
    # its three norms (192), dt 160 x 5120 + 5120, A 16 x 5120, D 5120, out
    mamba = 26_214_400 + 25_600 + 983_040 + 192 + 824_320 + 81_920 + 5_120 + 13_107_200
    assert counts.mamba_mixer_params(d) == mamba == 41_241_792
    assert counts.attention_mixer_params(d) == 2 * 6_553_600 + 2 * 327_680 == 13_762_560
    assert counts.mlp_params(d) == 3 * 2560 * 8192 == 62_914_560
    # a lane's slot: 26 x (16 + 3) x 5120 float32 = 10.1 MB whatever its length
    assert counts.state_bytes_per_lane(d) == 26 * 19 * 5120 * 4 == 10_117_120
    # read in every step, written once a dispatch of four: 1.25 passes a step
    # (a single-step program reads and writes: 2)
    assert counts.state_passes_a_step() == counts.state_passes_a_step(4) == 1.25
    assert counts.state_passes_a_step(1) == 2.0
    assert counts.state_step_bytes(d, 30) == 1.25 * 30 * 10_117_120 == 379_392_000
    assert counts.state_step_bytes(d, 30, horizon=1) == 2 * 30 * 10_117_120
    assert counts.scan_state_step_bytes(d, 30) == 1.25 * 30 * 26 * 16 * 5120 * 4 == 319_488_000
    # keys and values: 2 layers x 2 planes x 1 head x 128 = 512 values, 1,024 bytes
    assert counts.kv_values_per_token(d) == 512
    lanes, ctx = 30, 1500
    c = counts.step_counts(d, lanes, ctx)
    matmul = 26 * mamba + 2 * 13_762_560 + 28 * 62_914_560 + 2560 * 65536
    assert matmul == 3_029_191_552
    assert c["weight_bytes"] == 2 * matmul  # 6.06 GB
    assert c["state_bytes"] == 1.25 * lanes * 10_117_120  # 0.38 GB at 30 lanes
    assert c["kv_bytes"] == lanes * ctx * 1024 + lanes * 1024  # 46 MB
    assert c["bytes"] == c["weight_bytes"] + c["state_bytes"] + c["kv_bytes"] + lanes * 2560 * 2
    attn_ops = 4 * lanes * 2 * 20 * 128 * ctx
    scan_ops = 6 * lanes * 26 * 16 * 5120
    assert c["ops"] == 2 * lanes * matmul + attn_ops + scan_ops
    least, bound = counts.least_seconds(c, peaks_for("TPU v5 lite"))
    # 6.06 + 0.38 + 0.05 GB at 819 GB/s: 7.9 ms; the operations need 0.9 ms
    assert bound == "bytes" and 0.0078 < least < 0.0080
    # nothing live, nothing of the state or the cache to move
    idle = counts.step_counts(d, 0, 0)
    assert idle["state_bytes"] == idle["kv_bytes"] == 0 and idle["bytes"] == idle["weight_bytes"]


def test_mix_is_what_the_issue_names():
    mix = manifest.Cell(CELL).mix
    assert mix["generator"] == "stratified_open_loop" and mix["temperature"] == 0.7
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 256, "sigma": 1.0, "min": 32, "max": 2048}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 1024, "sigma": 0.5, "min": 256, "max": 2560}
    assert mix["interarrival"] == {"dist": "exponential"} and mix["block_requests"] == 15
    assert "top_p" not in mix and "top_k" not in mix
    assert mix["trace_seconds"] == 2 and mix["trace_offset_s"] == 5
    sets = gen.block_multisets(mix)
    assert min(sets["prompt_tokens"]) == 41 and max(sets["prompt_tokens"]) == 1602
    assert min(sets["output_tokens"]) == 409 and max(sets["output_tokens"]) == 2560
    assert sum(sets["prompt_tokens"]) == 5962 and sum(sets["output_tokens"]) == 17208
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


def made_up_ctx(ssm0, ssm1, ops=(), mixed_ops=()):
    """A run as `run.py` hands it to a reader: the ledger at the window's
    edges, one device plane with one decode_multi execution of 4 steps and
    one mixed_step execution."""
    plane = {
        "name": "/device:TPU:0", "span": (0.0, 2e9), "busy": [],
        "ops": list(ops) + list(mixed_ops),
        "modules": [["decode_multi", 1e6, 8e8, 0], ["mixed_step", 1e9, 5e8, 1]],
    }
    led = lambda ssm: {"steps_by_label": {}, **({"ssm": ssm} if ssm is not None else {})}
    return {
        "config": config(), "facts": {"device_kind": "TPU v5 lite", "decode_horizon": 4},
        "ledger0": led(ssm0), "ledger1": led(ssm1), "notes": {},
        "client": {"live": {"lanes": 30.0, "context": 1500.0}},
        "trace": {"planes": [plane], "busy_s": 0.5, "window_s": 2.0},
        "annotations": [
            ["loop.dispatch", 0.5e6, 9e8, {"label": "decode_multi@H4B64", "prefill_tokens": 0}],
            ["loop.dispatch", 0.99e9, 5.2e8, {"label": "mixed_step@c1", "prefill_tokens": 400}],
            ["loop.dispatch", 1.9e9, 5e8, {"label": "mixed_step@c2", "prefill_tokens": 900}],  # cut
        ],
    }


def metric(name):
    return manifest.load_json("cellbench", "metrics", name + ".json")["params"]


def test_reader_on_a_made_up_run():
    zero = {"layer_steps": 0, "slots_live": 0, "slot_resets": 0, "scan_tokens": 0}
    # 100 decode steps x 26 layers at 30 live lanes; 12 prompts of 4,800 tokens
    one = {"layer_steps": 2600, "slots_live": 3000, "slot_resets": 12, "scan_tokens": 4800}
    ops = [
        ["%select_dynamic-update-slice_fusion.5 = (f32[65,16,5120]{2,1,0:T(8,128)}, f32[64,5120]{1,0}) fusion(%a)", 2e6, 6e6],
        ["%fusion.77 = f32[65,16,5120]{2,1,0:T(8,128)} fusion(f32[65,16,5120] %s)", 1e7, 2e6],
        # the exposed wait on the compiler's copy of the same array counts
        ["%copy-done.4 = f32[65,16,5120]{2,1,0:S(1)} copy-done((f32[65,16,5120]{2,1,0}, u32[]) %c)", 2e7, 1e6],
        # reads the state, produces none: the product with C
        ["%multiply_reduce_fusion.12 = f32[64,5120]{1,0} fusion(f32[65,16,5120]{2,1,0:S(1)} %s)", 3e7, 3e6],
        # touches no state: the tail, a projection, the attention call
        ["%fusion.13 = f32[64,5120]{1,0} fusion(f32[65,15360]{1,0} %tail)", 4e7, 7e6],
        ["%tpu_custom_call.9 = bf16[64,20,128]{2,1,0} custom-call(s32[64,512] %t)", 5e7, 8e6],
    ]
    mixed_ops = [
        # a device loop's wrapper holds its body's time again: left out
        ["%while.7 = (s32[], f32[16,16,5120]{2,1,0}, f32[16,16,5120]{2,1,0}) while((s32[], f32[16,16,5120]) %t)", 1.05e9, 9e7],
        ["%multiply_reduce_fusion.201 = (f32[16,5120]{1,0}, f32[16,16,5120]{2,1,0}) fusion(f32[16,16,5120] %h)", 1.1e9, 3e7],
        ["%scatter.3 = f32[65,16,5120]{2,1,0} fusion(f32[65,16,5120] %states, f32[16,16,5120] %h)", 1.2e9, 1e7],
        ["%fusion.300 = bf16[512,8192]{1,0} fusion(%x)", 1.3e9, 4e7],
    ]
    ctx = made_up_ctx(zero, one, ops, mixed_ops)
    read = lambda name: ssm_layers.read(ctx, metric(name))
    assert read("state_slots_live") == pytest.approx(30.0)
    assert read("ssm_step_ms") == pytest.approx(12.0 / 4)  # 6 + 2 + 1 + 3 ms in 4 steps
    d = ref.dims(hf_config(config()))
    need = counts.scan_state_step_bytes(d, 30) / 819e9 * 1e3  # 0.39 ms
    assert read("ssm_step_roofline") == pytest.approx(100 * need / 3.0)
    assert 0 < read("ssm_step_roofline") < 100
    # 40 ms of scan operations for the 400 prompt tokens of the one mixed
    # step that lies whole inside the trace
    assert read("prefill_scan_ms_per_ktok") == pytest.approx(1000 * 40.0 / 400)
    assert ctx["notes"]["ssm_step_roofline"]["slots_live"] == pytest.approx(30.0)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_gives_nothing_where_there_is_nothing_to_read(name):
    """The parent's ledger has no `ssm`, an untraced run no trace, a dense
    model's trace no such operation: None, never an exception."""
    fusion = [["%fusion.1 = bf16[64,2048]{1,0} fusion(...)", 2e6, 5e6]]
    some = {"layer_steps": 26, "slots_live": 3, "slot_resets": 0, "scan_tokens": 0}
    zero = dict.fromkeys(some, 0)
    for ctx in (
        made_up_ctx(None, None, fusion),
        dict(made_up_ctx(None, None), trace=None),
        dict(made_up_ctx(None, None), ledger0=None, ledger1=None),
        made_up_ctx(zero, zero, fusion),
    ):
        assert ssm_layers.read(ctx, metric(name)) is None
    # the counters alone give the count and nothing that needs the trace
    ctx = dict(made_up_ctx(zero, some), trace=None)
    got = ssm_layers.read(ctx, metric(name))
    assert (got == pytest.approx(3.0)) if name == "state_slots_live" else got is None


def test_the_cell_resolves_and_describes():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "cellbench", "run.py"), "--workload", CELL, "--describe"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert doc["config"] == "jamba2-3b-bf16" and doc["chips"] == 1
    assert doc["reference"] == "hybrid_ssm" and doc["counts"] == "hybrid_ssm_decode"
    assert doc["traffic"] == "think-steady" and doc["generator"] == "stratified_open_loop"
    judged = {k for k, v in doc["metrics"].items() if v["group"] == "end_to_end"}
    assert judged == {"tpot_p50_ms", "setup_s"}
    for name in NEW_METRICS:
        assert doc["metrics"][name]["reader"] == "ssm_layers"
    # every all-cells per-layer metric is this cell's too
    bench = manifest.load_json("BENCHMARK.json")
    for entry in bench["per_layer"]:
        if "workloads" not in entry and entry["moves"] in ("tpot_p50_ms", "setup_s"):
            assert entry["name"] in doc["metrics"], entry["name"]
        if entry["name"] in NEW_METRICS:
            assert entry["workloads"] == [CELL]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == bench["workloads"][-1] and len(cell["why"]) <= 200


def test_reader_on_a_saved_slice_of_this_prs_own_trace():
    """0.65 s of the device plane of a traced run of the cell (my chip run,
    PR 38, call 7): three `decode_multi@H4B64` dispatches at 45 and 47 live
    lanes (the third is cut by the slice's edge and left out), four mixed
    steps of one chunk with 1,602 prompt tokens between them and a packed
    prefill of 97, with their annotations. The numbers are what the reader
    read of it when it was saved; the ledger's counters are made to match the
    annotations."""
    with gzip.open(os.path.join(DATA, "ssm_slice.jamba2-3b-bf16.think-steady.json.gz"), "rt") as f:
        saved = json.load(f)
    notes = [a[3] for a in saved["annotations"]]
    assert [n["label"] for n in notes] == (
        ["decode_multi@H4B64"] * 2 + ["mixed_step@c1"] * 4 + ["prefill_packed", "decode_multi@H4B64"])
    assert [n["state_slots"] for n in notes] == [45, 45, 46, 46, 46, 46, 47, 47]
    assert [n["prefill_tokens"] for n in notes] == [0, 0, 512, 512, 512, 66, 97, 0]
    led = lambda s: {"steps_by_label": {}, "ssm": s}
    steps = 3 * 4 + 5  # a horizon of four in each decode dispatch, one step in the others
    live = 4 * (45 + 45 + 47) + 4 * 46 + 47
    ctx = {
        "config": config(), "facts": {"device_kind": "TPU v5 lite", "decode_horizon": 4},
        "notes": {}, "client": {}, "trace": saved["trace"], "annotations": saved["annotations"],
        "ledger0": led(dict.fromkeys(("layer_steps", "slots_live", "slot_resets", "scan_tokens"), 0)),
        "ledger1": led({"layer_steps": 26 * steps, "slots_live": live, "slot_resets": 2, "scan_tokens": 1699}),
    }
    read = lambda name: ssm_layers.read(ctx, metric(name))
    assert read("state_slots_live") == pytest.approx(779 / 17)  # 45.8
    # a dispatch of four steps reads a layer's first state four times and
    # writes its last once (three products with C that recompute the steps
    # between, 32, 39 and 71 us, and one fusion that also stores, 67 us): 209
    # us a layer, 26 layers, four steps
    assert read("ssm_step_ms") == pytest.approx(1.366, abs=0.005)
    # 45.8 lanes' states, 1.25 passes a step: 0.49 GB, 0.60 ms at 819 GB/s
    assert ctx["notes"].get("ssm_step_roofline") is None
    assert read("ssm_step_roofline") == pytest.approx(43.6, abs=0.3)
    note = ctx["notes"]["ssm_step_roofline"]
    assert note["horizon"] == 4
    assert note["state_bytes_a_step"] == pytest.approx(1.25 * (779 / 17) * 26 * 16 * 5120 * 4)
    d = ref.dims(hf_config(config()))
    assert counts.scan_state_step_bytes(d, 48) == 1.25 * 48 * 26 * 16 * 5120 * 4 == 511_180_800
    # what the compiled horizon really moves for all 65 rows: 5 passes in 4
    # steps of 26 x 65 x 327,680 bytes, 0.69 GB a step in 1.366 ms: 507 GB/s,
    # 62% of the chip's 819, and never more than it
    moved = 1.25 * 65 * 26 * 16 * 5120 * 4
    assert 0.55 < moved / (read("ssm_step_ms") * 1e-3) / 819e9 < 0.70
    # 1,699 prompt tokens through the blocked scans of 26 layers; a chunk of
    # 66 tokens costs what one of 512 does
    assert read("prefill_scan_ms_per_ktok") == pytest.approx(85.9, abs=0.5)
    assert "ssm_layers_error" not in ctx["notes"]
    # and the all-cells kernel metric reads the two attention layers' calls
    from cellbench.readers import device_trace

    attn = device_trace.read(ctx, metric("attn_kernel_ms"))
    assert 0.2 < attn < 1.0
