"""BENCHMARK.json and the data files behind it: allowed characters, entries
that agree with their files, a harness that takes a new configuration, mix,
reader and metric as files with no edit, and a run that refuses to measure
without a chip."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from cellbench import manifest  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def bench() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def metric_entries():
    b = bench()
    return [(g, e) for g in ("end_to_end", "per_layer") for e in b[g]]


def test_top_level_keys_and_limits():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    assert all(os.path.isdir(os.path.join(REPO, p)) for p in b["paths"])
    for word in b["command"]:
        assert not word.startswith("/") and ".." not in word
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(b["workloads"]) // 4)


@pytest.mark.parametrize("group,entry", metric_entries(),
                         ids=[e["name"] for _, e in metric_entries()])
def test_metric_entry_agrees_with_its_file(group, entry):
    assert NAME.match(entry["name"]) and UNIT.match(entry["unit"])
    assert entry["better"] in ("lower", "higher") and entry["source"] in SOURCES
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if group == "end_to_end" else {"layer", "moves"}
    assert set(entry) <= allowed
    m = manifest.load_json("cellbench", "metrics", entry["name"] + ".json")
    for key in ("name", "unit", "better", "source"):
        assert m[key] == entry[key], key
    manifest.reader(m["reader"])  # importable
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    assert set(entry.get("workloads", cells)) <= cells
    if group == "end_to_end":
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0.01 <= entry["bound"] <= 0.1
    else:
        assert m["layer"] == entry["layer"] and "\n" not in entry["layer"]
        e2e = {e["name"]: e for e in b["end_to_end"]}
        assert entry["moves"] in e2e
        # every cell that reads this metric reports the metric it moves
        moved = set(e2e[entry["moves"]].get("workloads", cells))
        assert set(entry.get("workloads", cells)) <= moved


@pytest.mark.parametrize("cell", [w["name"] for w in bench()["workloads"]])
def test_cell_resolves_and_reports_enough(cell):
    c = manifest.Cell(cell)
    assert NAME.match(cell) and NAME.match(c.entry["traffic"]) and NAME.match(c.entry["config"])
    assert len(c.entry["why"]) <= 200 and c.chips in (1, 4)
    e2e = [m["name"] for m in c.metrics("end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2 and len(c.metrics("per_layer")) >= 1
    assert c.generator().generate(c.mix, 1, 5.0, c.config["vocab_size"])


@pytest.mark.parametrize("cfg", [c["name"] for c in bench()["configs"]])
def test_configuration_file_states_its_cuts(cfg):
    entry = next(c for c in bench()["configs"] if c["name"] == cfg)
    assert entry["file"].startswith("cellbench/") and entry["source"].startswith("https://")
    doc = manifest.load_json(entry["file"])
    assert sorted(doc["bench"]["reduced"]) == sorted(entry["reduced"])
    assert doc["bench"]["source"] == entry["source"] and doc["bench"]["name"] == cfg
    # no width may be cut
    for key in entry["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size"))
    assert doc["bench"]["check"]["tolerance_rms_rel"] > 0
    for key in ("assumed", "server", "reference", "counts", "weights_seed"):
        assert key in doc["bench"]


def test_files_under_paths_have_allowed_names():
    for path in bench()["paths"]:
        for root, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                if name.endswith(".pyc"):
                    continue
                assert re.match(r"^[A-Za-z0-9_.\-]+$", name), os.path.join(root, name)


def run_cli(root: str, *args: str, timeout: float = 240) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    return subprocess.run(
        [sys.executable, os.path.join(root, "cellbench", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout,
    )


def last_json(stdout: str):
    lines = [l for l in stdout.splitlines() if l.startswith("{")]
    return json.loads(lines[-1]) if lines else None


@pytest.fixture()
def copy(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "cellbench"), root / "cellbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def test_new_configuration_mix_reader_and_metric_are_found_with_no_edit(copy):
    """What a later PR does: add files and entries, edit no file."""
    before = {
        os.path.relpath(os.path.join(r, f), copy): open(os.path.join(r, f), "rb").read()
        for r, _, fs in os.walk(copy / "cellbench") for f in fs
    }
    cfg = manifest.load_json("cellbench", "configs", "rehearsal-tiny.json")
    cfg["bench"]["name"] = "added-model"
    (copy / "cellbench" / "configs" / "added-model.json").write_text(json.dumps(cfg))
    mix = manifest.load_json("cellbench", "traffic", "chat-steady.json")
    mix.update(generator="added_generator", rate_rps=3.0)
    (copy / "cellbench" / "traffic" / "added-mix.json").write_text(json.dumps(mix))
    (copy / "cellbench" / "generators" / "added_generator.py").write_text(
        "from cellbench.generators.stratified_open_loop import generate  # noqa: F401\n"
    )
    (copy / "cellbench" / "readers" / "added_reader.py").write_text(
        "def read(ctx, params):\n    return ctx['client'].get(params['field'])\n"
    )
    (copy / "cellbench" / "metrics" / "added_metric.json").write_text(json.dumps({
        "name": "added_metric", "unit": "ms", "better": "lower", "source": "host_clock",
        "layer": "admission and queue", "moves": "tpot_p50_ms",
        "reader": "added_reader", "params": {"field": "ttft_p90_ms"},
    }))
    b = json.loads((copy / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "added-model", "source": "https://example.org/x",
                         "file": "cellbench/configs/added-model.json", "reduced": [],
                         "why": "test"})
    b["workloads"].append({"name": "added-model.added-mix", "config": "added-model",
                           "traffic": "added-mix", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "added_metric", "unit": "ms", "better": "lower",
                           "source": "host_clock", "layer": "admission and queue",
                           "moves": "tpot_p50_ms", "workloads": ["added-model.added-mix"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(b))
    cp = run_cli(str(copy), "--workload", "added-model.added-mix", "--describe")
    assert cp.returncode == 0, cp.stderr
    got = last_json(cp.stdout)
    assert got["config"] == "added-model" and got["generator"] == "added_generator"
    assert got["metrics"]["added_metric"] == {
        "group": "per_layer", "reader": "added_reader", "unit": "ms"}
    assert "tpot_p50_ms" in got["metrics"] and "out_tok_s" not in got["metrics"]
    assert got["requests_at_10s"] == round(3.0 * (mix["ramp_s"] + 10.0))
    for path, content in before.items():
        assert open(os.path.join(copy, path), "rb").read() == content, path


def test_refuses_where_only_the_benchmark_is_there(copy):
    """BENCHMARK.json and the files under paths alone: no program to
    measure, so no result and a code other than 0."""
    cp = run_cli(str(copy), "--workload", "mistral7b-int8.chat-steady",
                 "--seed", "1", "--seconds", "1", "--trace", "0")
    assert cp.returncode != 0
    assert last_json(cp.stdout) is None or "correct" not in last_json(cp.stdout)


def test_unknown_cell_is_an_error():
    cp = run_cli(REPO, "--workload", "no-such.cell", "--describe")
    assert cp.returncode != 0 and "correct" not in cp.stdout


def test_refuses_to_measure_without_a_chip():
    """Here JAX finds no TPU: the server child is told to use the TPU and
    nothing else, fails at start-up, and the run prints no result."""
    cp = run_cli(REPO, "--workload", "mistral7b-int8.chat-steady",
                 "--seed", "3000000000", "--seconds", "2", "--trace", "0")
    assert cp.returncode != 0
    assert "cellbench FAILED" in cp.stderr
    for line in cp.stdout.splitlines():
        assert '"correct"' not in line and '"metrics"' not in line


@pytest.mark.timeout(600)
def test_cpu_rehearsal_drives_the_whole_run():
    """The same code on a toy model on the CPU: every phase, the contract's
    keys, and a line that says it is no result."""
    cp = run_cli(REPO, "--workload", "mistral7b-int8.chat-steady", "--seed", "2147483700",
                 "--seconds", "6", "--trace", "1", "--cpu-rehearsal", timeout=500)
    assert cp.returncode == 0, cp.stderr[-3000:] + cp.stdout[-2000:]
    line = last_json(cp.stdout)
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device", "breakdown"}
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    for name in ("gen_lateness_p99_ms", "ttft_p90_ms", "gap_p99_ms", "mixed_step_share",
                 "decode_step_ms", "prefill_ms_per_ktok"):
        assert name in line["metrics"], name
    # no device trace on a CPU: the device readers return nothing
    assert "decode_device_ms" not in line["metrics"]
    phases = [json.loads(l).get("phase") for l in cp.stdout.splitlines()[:-1] if l.startswith("{")]
    assert phases == ["start", "ready", "warm", "stopped", "check", "window"]
    check = next(json.loads(l) for l in cp.stdout.splitlines() if '"phase": "check"' in l)
    assert check["rms_rel"] <= check["limit"] and check["streams_broken"] == 0
