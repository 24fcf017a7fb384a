"""`chip_smoke.py` cannot rot, and nothing on its path hides the device.

The smoke itself needs a TPU; here its `--cpu-rehearsal` mode drives the same
code (server child through `dynamo_tpu.run in=http out=jax`, streamed
requests, goodput-ledger checks, clean shutdown, logits child) on a tiny
model with interpret kernels. Beside it: the no-fallback rules of PR 21
(`chip_smoke.py` fails off-TPU, the peak table has no default)
and the one rule for where the compilation cache lives.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _child_env(**extra) -> dict:
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env.update(extra)
    return env


def _json_lines(stdout: str) -> list[dict]:
    return [
        json.loads(line) for line in stdout.splitlines()
        if line.startswith("{")
    ]


@pytest.mark.timeout(600)
def test_cpu_rehearsal_runs_every_phase():
    proc = subprocess.run(
        [sys.executable, SMOKE, "--cpu-rehearsal"],
        capture_output=True, text=True, timeout=570, cwd=REPO,
        env=_child_env(),
    )
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout[-3000:]}\nstderr:\n{proc.stderr[-3000:]}"
    )
    lines = _json_lines(proc.stdout)
    # the last line is the contract's and nothing more; a rehearsal says cpu
    assert lines[-1] == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    by_phase = {l["phase"]: l for l in lines[:-1]}
    labels = by_phase["ledger"]["dispatches_by_label"]
    assert any(l.startswith("mixed_step@c") for l in labels), labels
    assert any(l.startswith("decode_multi@H4B") for l in labels), labels
    assert "prefill_packed" in labels or "prefill_chunk" in labels, labels
    # one horizon program, not one per tail length
    assert sum(l.startswith("decode_multi@") for l in labels) == 1, labels
    assert by_phase["ledger"]["recompiles"] == {}
    requests = [l for l in lines if l.get("phase") == "request"]
    assert len(requests) >= 12 and all(r["ok"] for r in requests)
    assert by_phase["shutdown"]["rc"] == 0
    assert by_phase["logits"]["compared"] == ["pallas", "xla"]
    # compared at the batch and block-table width the server was given
    assert (by_phase["logits"]["batch"], by_phase["logits"]["table_blocks"]) \
        == (8, 2048 // 16)
    assert by_phase["logits"]["max_rel_diff"] <= by_phase["logits"][
        "tolerance_rel"
    ]


@pytest.mark.timeout(120)
def test_smoke_fails_where_jax_finds_no_accelerator():
    proc = subprocess.run(
        [sys.executable, SMOKE], capture_output=True, text=True,
        timeout=100, cwd=REPO, env=_child_env(),
    )
    assert proc.returncode != 0
    assert not any("ok" in l for l in _json_lines(proc.stdout)), proc.stdout
    assert "needs 'tpu'" in proc.stderr


def _import_from_root(name: str):
    sys.path.insert(0, REPO)
    try:
        return __import__(name)
    finally:
        sys.path.remove(REPO)


_HEALTHY_LEDGER = {
    "steps_by_label": {
        "prefill_packed": {"count": 4}, "mixed_step@c2": {"count": 5},
        "decode_multi@H4B64": {"count": 18}, "decode": {"count": 1},
    },
    "recompiles": {},
}
_FACTS = {
    "decode_horizon": 4, "mixed_step": True, "kv_quantized": False,
    "attn_impl": "pallas",
}


@pytest.mark.parametrize(
    "single_steps, fails_with",
    [(1, None), (40, "single-step decode dispatches")],
    ids=["healthy", "too_many_single_steps"],
)
def test_ledger_check_catches_a_horizon_dropped_mid_run(
    single_steps, fails_with
):
    """One decode_multi@H4 dispatch is not enough: a run that fell to H=1
    after it must fail too."""
    smoke = _import_from_root("chip_smoke")
    goodput = json.loads(json.dumps(_HEALTHY_LEDGER))
    goodput["steps_by_label"]["decode"]["count"] = single_steps
    args = (goodput, _FACTS, False, 15)
    if fails_with is None:
        smoke.check_ledger(*args)
    else:
        with pytest.raises(smoke.SmokeFailure, match=fails_with):
            smoke.check_ledger(*args)


# ------------------------------------------------- the compilation cache rule

_REPORT = (
    "import json, jax\n"
    "from dynamo_tpu.runtime.config import jax_cache_dir, "
    "setup_jax_compilation_cache\n"
    "print(json.dumps([jax_cache_dir(), setup_jax_compilation_cache(), "
    "jax.config.jax_compilation_cache_dir]))\n"
)


def _cache_dirs(env: dict, cwd: str) -> list[str]:
    out = subprocess.run(
        [sys.executable, "-c", _REPORT], env=env, cwd=cwd, check=True,
        capture_output=True, text=True, timeout=100,
    ).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_cache_dir_from_outside_is_used_as_is(tmp_path):
    outside = str(tmp_path / "placed-from-outside")
    env = _child_env(JAX_COMPILATION_CACHE_DIR=outside)
    assert _cache_dirs(env, str(tmp_path)) == [outside] * 3


def test_cache_dir_default_is_the_checkout(tmp_path):
    env = _child_env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    # from any working directory, and whatever the home directory is
    env["HOME"] = str(tmp_path)
    assert _cache_dirs(env, str(tmp_path)) == [
        os.path.join(REPO, ".jax_cache")
    ] * 3


async def test_serve_passes_outside_cache_dir_to_children(monkeypatch):
    """serve.py's children inherit JAX_COMPILATION_CACHE_DIR untouched and
    get no cache variable of the program's own."""
    from dynamo_tpu import serve
    from dynamo_tpu.sdk import Supervisor

    async def nothing(*args, **kwargs):
        return None

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    monkeypatch.setattr(Supervisor, "start_all", nothing)
    monkeypatch.setattr(serve, "_wait_port", nothing)
    sup = await serve.serve_graph(
        "dynamo_tpu.graphs.agg", fabric_addr="127.0.0.1:1"
    )
    assert sup.procs, "the graph started no service"
    for proc in sup.procs.values():
        assert proc.env["JAX_COMPILATION_CACHE_DIR"] == "/placed/from/outside"
        assert not any("JAX_CACHE" in k for k in proc.env), sorted(proc.env)
