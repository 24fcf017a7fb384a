#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that the serving path starts on the chip.

    python chip_smoke.py                 # one TPU chip (what the driver runs)
    python chip_smoke.py --four-chips    # tp=4 on a four-chip host, only that
    python chip_smoke.py --cpu-rehearsal # tiny model, CPU, interpret kernels

Serves **Mistral-7B-v0.1 at its published widths** (hidden 4096,
intermediate 14336, 32 layers, 32 query / 8 KV heads, head 128, vocab 32000,
sliding window 4096, rope theta 10000) through the normal entry point,

    python -m dynamo_tpu.run in=http out=jax --model-path DIR \
        --context-length 4096 --max-batch 64

with every default the engine has on a TPU and one switch,
`DYN_JAX_QUANTIZE_INT8=1` (int8 weight-only: the only way 7B weights and a
useful KV cache share one 16 GB chip). Weights are random, made from
`--seed`; the model directory (config + a generated word-level tokenizer)
and the prompts are made from the same seed. Three phases:

1. *serve*: start the server as a child, wait for readiness, stream
   `/v1/completions` and `/v1/chat/completions` requests (short and ~2k-token
   prompts, enough of them at once that packed prefill, mixed prefill+decode
   steps and the H=4 decode horizon all dispatch), check that every stream
   ends with exactly the requested number of tokens, read the engine's
   goodput ledger (`GET /debug/goodput`), shut the server down with SIGINT.
2. *the programs that were meant to run did*: from the ledger's labels, a
   `mixed_step@c*`, a `decode_multi@H4B*` and a packed or chunked prefill
   were dispatched, attention was Pallas, nothing compiled twice, and a cold
   compilation cache gained entries.
3. *right, not merely alive*: after the server has exited, a second child
   builds the same seeded weights and runs one prefill and a few decode
   steps through `ModelRunner` with `attn_impl="pallas"` and `"xla"`
   (`--four-chips`: tp=4 against one device), at the server's batch and
   block-table width; logits agree within the tolerance stated below. The
   decode logits come from `llama.decode` under this script's own
   `jax.jit` (the runner's decode programs return samples, not logits).

One process holds the chip at a time: this parent never imports jax, and
every device fact it prints was printed first by the child that held the
chip. Earlier output lines are one JSON object each; their timings are
**smoke timings, not results**. The last line is the contract's:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Any failure exits non-zero and prints no such line. `--cpu-rehearsal` exists
so the script cannot rot where there is no chip: it prints
`"platform": "cpu"` and is not a pass on the chip machine.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
# model directory and the children's logs; a rehearsal keeps its own, so
# that it never overwrites what a chip run brought back
OUT = os.path.join(
    ROOT, "chiprun_out",
    "chip_smoke_rehearsal" if "--cpu-rehearsal" in sys.argv else "chip_smoke",
)

# mistralai/Mistral-7B-v0.1 config.json, as published
MISTRAL_7B = {
    "architectures": ["MistralForCausalLM"],
    "model_type": "mistral",
    "bos_token_id": 1,
    "eos_token_id": 2,
    "hidden_act": "silu",
    "hidden_size": 4096,
    "intermediate_size": 14336,
    "max_position_embeddings": 32768,
    "num_attention_heads": 32,
    "num_hidden_layers": 32,
    "num_key_value_heads": 8,
    "rms_norm_eps": 1e-05,
    "rope_theta": 10000.0,
    "sliding_window": 4096,
    "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
    "vocab_size": 32000,
}
# the CPU rehearsal's stand-in: same keys, toy sizes (head 16, window 64;
# 4 KV heads so that tp=4 divides them)
TINY = dict(
    MISTRAL_7B, hidden_size=128, intermediate_size=256, num_attention_heads=8,
    num_hidden_layers=2, num_key_value_heads=4, vocab_size=1000,
    sliding_window=64, max_position_embeddings=2048,
)

# Logits of the two attention paths (or of tp=4 and one device) are compared
# as max |a - b| over the vocabulary, relative to max |logit|: bf16
# activations through 32 layers with different summation orders agree to a
# few bf16 ulps of the largest logit, far below the gap a wrong mask, a
# missed page or a dropped shard would open (order 1).
LOGITS_REL_TOL = 0.05


def served_shape(rehearsal: bool) -> tuple[int, int]:
    """(--max-batch, --context-length) of the server; the logits phase
    compares at the same batch and block-table width."""
    return (8, 2048) if rehearsal else (64, 4096)


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------------ made from seed


def write_model_dir(path: str, config: dict) -> int:
    """config.json plus a word-level tokenizer covering the whole
    vocabulary (`benchmarks/perf_sweep.make_tiny_model_dir` at full width).
    Returns the number of words."""
    from tokenizers import Tokenizer, models, pre_tokenizers

    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(config, f, indent=1)
    vocab = {"<unk>": 0, "<s>": 1, "</s>": 2}
    words = config["vocab_size"] - len(vocab)
    for i in range(words):
        vocab[f"w{i}"] = 3 + i
    tok = Tokenizer(models.WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    tok.save(os.path.join(path, "tokenizer.json"))
    return words


def prompt_text(rng: random.Random, n_tokens: int, words: int) -> str:
    return " ".join(f"w{rng.randrange(words)}" for _ in range(n_tokens))


# ------------------------------------------------------------------ children


def child_env(rehearsal: bool, four: bool) -> dict:
    """The environment a user's shell would give the server: no DYN_*
    variable but the int8-weights switch. The rehearsal adds what makes a
    CPU behave like the chip's defaults (interpret kernels, horizon 4)."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("DYN_")}
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["DYN_JAX_QUANTIZE_INT8"] = "1"
    env["PYTHONFAULTHANDLER"] = "1"  # a hung child dumps its stacks on SIGABRT
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
        env["DYN_ATTN_IMPL"] = "pallas_interpret"
        env["DYN_DECODE_HORIZON"] = "4"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={4 if four else 1}"
        )
    return env


def probe_device(env: dict) -> dict:
    """A short-lived child names the device before anything is built, so a
    machine without a chip fails in seconds, not after a 7B init on its
    CPU. It has exited (and released the chip) before the server starts."""
    code = (
        "import json, jax, jaxlib, importlib.metadata as m\n"
        "d = jax.devices()\n"
        "try: libtpu = m.version('libtpu')\n"
        "except m.PackageNotFoundError: libtpu = None\n"
        "print(json.dumps({'platform': d[0].platform, 'kind': d[0].device_kind,"
        " 'count': len(d), 'jax': jax.__version__,"
        " 'jaxlib': jaxlib.__version__, 'libtpu': libtpu}))\n"
    )
    cp = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=300,
    )
    check(cp.returncode == 0, f"device probe failed:\n{cp.stderr[-2000:]}")
    return json.loads(cp.stdout.strip().splitlines()[-1])


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def built_facts(log_path: str) -> dict:
    """The line factory.build_jax_engine logs when the engine is built."""
    marker = "jax engine built: "
    with open(log_path, errors="replace") as f:
        for line in f:
            if marker in line:
                return json.loads(line[line.index(marker) + len(marker):])
    raise SmokeFailure("the server never logged 'jax engine built'")


def http_json(port: int, path: str, timeout: float = 30.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        body = resp.read()
        return resp.status, (json.loads(body) if body else None)
    finally:
        conn.close()


def wait_ready(proc: subprocess.Popen, port: int, log: str, budget_s: float):
    deadline = time.monotonic() + budget_s
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise SmokeFailure(
                f"server exited rc={proc.returncode} before it was ready:\n"
                + tail(log)
            )
        try:
            status, _ = http_json(port, "/health", timeout=5.0)
            if status == 200:
                return
        except OSError:
            pass
        time.sleep(1.0)
    raise SmokeFailure(f"server not ready after {budget_s:.0f}s:\n" + tail(log))


class Stream(threading.Thread):
    """One streamed OpenAI request; counts what came back."""

    def __init__(self, port, name, path, body, want_tokens, timeout):
        super().__init__(daemon=True, name=name)
        self.port, self.path, self.body = port, path, body
        self.want_tokens, self.timeout = want_tokens, timeout
        self.first_chunk = threading.Event()
        self.chunks = 0
        self.usage = None
        self.finish_reason = None
        self.error = None
        self.seconds = None

    def run(self) -> None:
        t0 = time.monotonic()
        try:
            conn = http.client.HTTPConnection(
                "127.0.0.1", self.port, timeout=self.timeout
            )
            conn.request(
                "POST", self.path, body=json.dumps(self.body),
                headers={"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            if resp.status != 200:
                raise SmokeFailure(
                    f"HTTP {resp.status}: {resp.read()[:500]!r}"
                )
            event = None
            for raw in resp:
                line = raw.decode("utf-8", "replace").rstrip("\r\n")
                if line.startswith("event:"):
                    event = line[6:].strip()
                    continue
                if not line.startswith("data: "):
                    continue
                data = line[6:]
                if data == "[DONE]":
                    break
                if event == "error":
                    raise SmokeFailure(f"stream error event: {data[:500]}")
                doc = json.loads(data)
                if doc.get("usage"):
                    self.usage = doc["usage"]
                for choice in doc.get("choices") or []:
                    if choice.get("finish_reason"):
                        self.finish_reason = choice["finish_reason"]
                    if choice.get("text") or (
                        choice.get("delta") or {}
                    ).get("content"):
                        self.chunks += 1
                        self.first_chunk.set()
            conn.close()
        except Exception as e:  # noqa: BLE001 — reported by verdict()
            self.error = f"{type(e).__name__}: {e}"
        finally:
            self.first_chunk.set()
            self.seconds = time.monotonic() - t0

    def verdict(self) -> dict:
        got = (self.usage or {}).get("completion_tokens")
        ok = (
            self.error is None
            and got == self.want_tokens
            and self.finish_reason == "length"
        )
        return {
            "request": self.name, "ok": ok, "want_tokens": self.want_tokens,
            "completion_tokens": got,
            "prompt_tokens": (self.usage or {}).get("prompt_tokens"),
            "finish_reason": self.finish_reason, "error": self.error,
            "smoke_seconds": round(self.seconds or 0.0, 2),
        }


def make_request(port, name, kind, prompt, max_tokens, model, timeout):
    body = {
        "model": model, "max_tokens": max_tokens, "stream": True,
        "temperature": 0.7,
        "stream_options": {"include_usage": True},
        # fixed-length generation: random weights may sample EOS
        "ext": {"ignore_eos": True},
    }
    if kind == "chat":
        body["messages"] = [{"role": "user", "content": prompt}]
        path = "/v1/chat/completions"
    else:
        body["prompt"] = prompt
        path = "/v1/completions"
    return Stream(port, name, path, body, max_tokens, timeout)


def drive_requests(port, model, words, rng, rehearsal, timeout) -> list[dict]:
    """Wave 1: short prompts at once (packed prefill, then the decode
    horizon). Wave 2: more short ones, and as soon as each has streamed a
    first token, the long prompts land on a decoding batch (mixed
    prefill+decode steps; a ~2k-token prompt is four 512-token chunks)."""
    long_len = [1100, 700] if rehearsal else [2000, 1900, 1100]
    short_out, long_out = (12, 8) if rehearsal else (48, 16)

    def short(i, wave, out):
        return make_request(
            port, f"{wave}-short-{i}", "chat" if i % 3 == 2 else "completion",
            prompt_text(rng, rng.randrange(12, 40), words), out, model, timeout,
        )

    verdicts = []
    wave1 = [short(i, "w1", 24 if not rehearsal else 8) for i in range(6)]
    for s in wave1:
        s.start()
    for s in wave1:
        s.join()
        verdicts.append(s.verdict())
    wave2 = [short(i, "w2", short_out) for i in range(6)]
    for s in wave2:
        s.start()
    for s in wave2:
        s.first_chunk.wait(timeout)
    longs = [
        make_request(
            port, f"w2-long-{n}", "chat" if j == 1 else "completion",
            prompt_text(rng, n, words), long_out, model, timeout,
        )
        for j, n in enumerate(long_len)
    ]
    for s in longs:
        s.start()
    for s in wave2 + longs:
        s.join()
        verdicts.append(s.verdict())
    return verdicts


def cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir) if not n.startswith("."))
    except OSError:
        return 0


def check_ledger(
    goodput: dict, facts: dict, rehearsal: bool, n_streams: int
) -> None:
    """The programs that were meant to run did. A run that answered every
    request on the XLA gather, or at H=1, fails here; so does one that
    dropped to H=1 after its first horizon dispatch."""
    steps = goodput["steps_by_label"]
    labels = sorted(steps)
    check(
        any(l.startswith("mixed_step@c") for l in labels),
        f"no mixed_step@c* dispatched: {labels}",
    )
    check(
        any(l.startswith("decode_multi@H4B") for l in labels),
        f"no decode_multi@H4B* dispatched (engine fell to H=1?): {labels}",
    )
    # single steps are for last tokens only (every live lane on its last
    # one), so there are never more of them than streams
    single = steps.get("decode", {}).get("count", 0)
    check(
        single <= n_streams,
        f"{single} single-step decode dispatches for {n_streams} streams "
        "(the horizon was dropped mid-run?)",
    )
    check(
        any(l in ("prefill_packed", "prefill_chunk") for l in labels),
        f"no packed or chunked prefill dispatched: {labels}",
    )
    # a `stall` is the host's, not a compile (telemetry/goodput.py)
    compiled_twice = {
        k: v for k, v in goodput["recompiles"].items()
        if not k.endswith("|stall")
    }
    check(not compiled_twice, f"a program compiled twice: {compiled_twice}")
    check(facts["decode_horizon"] == 4, f"decode horizon {facts}")
    check(facts["mixed_step"] is True, f"mixed steps off: {facts}")
    check(not facts["kv_quantized"], "KV cache is not bf16")
    if not rehearsal:
        # "pallas", never "pallas_interpret": no interpret= on a TPU
        check(
            facts["attn_impl"] == "pallas",
            f"attention is {facts['attn_impl']!r}, not the Pallas kernels",
        )


def serve_phase(args, env, model_dir, words, device) -> dict:
    from dynamo_tpu.runtime.config import jax_cache_dir  # imports no jax

    rehearsal = args.cpu_rehearsal
    rng = random.Random(args.seed)
    cache_dir = jax_cache_dir()
    before = cache_entries(cache_dir)
    emit(
        phase="cache", cache_dir=cache_dir, entries_before=before,
        from_env="JAX_COMPILATION_CACHE_DIR" in os.environ,
    )
    port = free_port()
    model = "mistral-7b-smoke"
    log = os.path.join(OUT, "server.log")
    max_batch, context = served_shape(rehearsal)
    cmd = [
        sys.executable, "-m", "dynamo_tpu.run", "in=http", "out=jax",
        "--model-path", model_dir, "--model-name", model,
        "--http-host", "127.0.0.1", "--http-port", str(port),
        "--context-length", str(context), "--max-batch", str(max_batch),
    ]
    if args.four_chips:
        cmd += ["--tensor-parallel-size", "4"]
    emit(phase="serve", cmd=" ".join(cmd[1:]), log=log)
    t0 = time.monotonic()
    with open(log, "w") as logf:
        proc = subprocess.Popen(
            cmd, env=env, cwd=ROOT, stdout=logf, stderr=subprocess.STDOUT
        )
    try:
        wait_ready(proc, port, log, budget_s=600.0)
        ready_s = time.monotonic() - t0
        facts = built_facts(log)
        emit(
            phase="ready", smoke_seconds_to_ready=round(ready_s, 1),
            engine=facts,
        )
        check(
            (facts["platform"], facts["device_kind"], facts["device_count"])
            == (device["platform"], device["kind"], device["count"]),
            f"server saw {facts}, the probe saw {device}",
        )
        check(
            facts["cache_dir"] == cache_dir,
            f"server caches in {facts['cache_dir']}, expected {cache_dir}",
        )
        if args.four_chips:
            check(facts["mesh"] and facts["mesh"].get("tp") == 4, str(facts))
            in_use = facts["bytes_in_use"]
            check(
                len(in_use) == 4
                and (rehearsal or min(in_use) > 0.5 * max(in_use)),
                f"weights and cache are not spread over four devices: {in_use}",
            )
        t1 = time.monotonic()
        verdicts = drive_requests(
            port, model, words, rng, rehearsal, timeout=900.0
        )
        for v in verdicts:
            emit(phase="request", **v)
        requests_s = time.monotonic() - t1
        status, body = http_json(port, "/debug/goodput")
        check(status == 200 and body and body.get("goodput"), str(body))
        goodput = body["goodput"]
        emit(
            phase="ledger",
            smoke_compile_seconds_by_label=goodput["compile_s_by_label"],
            dispatches_by_label={
                k: v["count"] for k, v in goodput["steps_by_label"].items()
            },
            smoke_p50_ms_by_label={
                k: v["p50_ms"] for k, v in goodput["steps_by_label"].items()
            },
            recompiles=goodput["recompiles"],
            mixed_steps=goodput["mixed_steps"],
            smoke_seconds_requests=round(requests_s, 1),
        )
        bad = [v for v in verdicts if not v["ok"]]
        check(not bad, f"{len(bad)} of {len(verdicts)} streams failed: {bad}")
        check_ledger(goodput, facts, rehearsal, len(verdicts))
    except BaseException:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        raise
    # clean shutdown: SIGINT is what a user's ctrl-c sends the entry point
    t2 = time.monotonic()
    proc.send_signal(signal.SIGINT)
    try:
        rc = proc.wait(timeout=60.0)
    except subprocess.TimeoutExpired:
        proc.send_signal(signal.SIGABRT)  # faulthandler: every thread's stack
        proc.wait()
        raise SmokeFailure(
            "server still running 60 s after SIGINT:\n" + tail(log, 120)
        )
    check(rc == 0, f"server exited rc={rc} on SIGINT:\n" + tail(log))
    after = cache_entries(cache_dir)
    compile_s = sum(goodput["compile_s_by_label"].values())
    emit(
        phase="shutdown", rc=rc,
        smoke_seconds_to_exit=round(time.monotonic() - t2, 1),
        cache_entries_before=before, cache_entries_after=after,
        smoke_compile_seconds_total=round(compile_s, 1),
    )
    # setup_jax_compilation_cache cannot fail silently past this: a cold
    # run that leaves the directory as it found it was not caching
    check(
        before > 0 or after > before,
        f"cold run left no entry in {cache_dir}",
    )
    return facts


def logits_phase(args, env, model_dir) -> dict:
    cmd = [
        sys.executable, os.path.abspath(__file__), "--logits-child", model_dir,
        "--seed", str(args.seed),
    ]
    if args.four_chips:
        cmd.append("--four-chips")
    if args.cpu_rehearsal:
        cmd.append("--cpu-rehearsal")
    # each runner is told its attention path; DYN_ATTN_IMPL (which the
    # rehearsal's server needs) would override both
    env = {k: v for k, v in env.items() if k != "DYN_ATTN_IMPL"}
    t0 = time.monotonic()
    log = os.path.join(OUT, "logits.log")
    with open(log, "w") as logf:
        cp = subprocess.run(
            cmd, env=env, cwd=ROOT, stdout=subprocess.PIPE, text=True,
            stderr=logf, timeout=900,
        )
    check(cp.returncode == 0, f"logits child rc={cp.returncode}:\n" + tail(log))
    result = json.loads(cp.stdout.strip().splitlines()[-1])
    emit(
        phase="logits", smoke_seconds=round(time.monotonic() - t0, 1),
        tolerance_rel=LOGITS_REL_TOL, **result,
    )
    check(result["finite"], "non-finite logits")
    check(
        result["max_rel_diff"] <= LOGITS_REL_TOL,
        f"logits differ by {result['max_rel_diff']} of the largest logit "
        f"(> {LOGITS_REL_TOL})",
    )
    return result


# ---------------------------------------------------- the logits child (jax)


def logits_child(args) -> None:
    """One prefill and a few decode steps on the same seeded weights through
    two ModelRunners: Pallas attention against the XLA gather on one device,
    or (--four-chips) tp=4 against one device, both Pallas. The runners'
    own programs build the KV; the logits of each step come from the same
    model functions under the runner's pinned config."""
    import jax
    import numpy as np

    from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner
    from dynamo_tpu.engine.jax_engine.weights import load_or_init_params
    from dynamo_tpu.models import llama
    from dynamo_tpu.runtime.config import setup_jax_compilation_cache

    setup_jax_compilation_cache()
    rehearsal = args.cpu_rehearsal
    model_dir = args.logits_child
    config = llama.LlamaConfig.from_model_dir(model_dir)
    params = load_or_init_params(
        model_dir, config, quantize=True, seed=args.seed
    )
    pallas = "pallas_interpret" if rehearsal else "pallas"
    # the served batch and block-table width: the kernels' grids and the
    # gather are the ones the server's decode programs run (two lanes
    # live, the others idle, as in a lightly loaded server)
    (B, max_len), bs = served_shape(rehearsal), 16
    common = dict(
        num_blocks=2 * (max_len // bs) + 8, block_size=bs, max_batch=B,
        max_model_len=max_len, rng_seed=args.seed,
    )
    if args.four_chips:
        from dynamo_tpu.parallel.mesh import build_mesh
        from dynamo_tpu.parallel.sharding import shard_llama

        mesh = build_mesh(tp=4)
        sharded, kv_sharding = shard_llama(mesh, config, params)
        runners = {
            "tp4": ModelRunner(
                config, sharded, attn_impl=pallas, mesh=mesh,
                kv_sharding=kv_sharding, **common,
            ),
            "one_device": ModelRunner(
                config, params, attn_impl=pallas, **common
            ),
        }
    else:
        runners = {
            "pallas": ModelRunner(config, params, attn_impl=pallas, **common),
            "xla": ModelRunner(config, params, attn_impl="xla", **common),
        }
    rng = np.random.default_rng(args.seed)
    prompts = [
        rng.integers(3, config.vocab_size, size=n).tolist()
        for n in ((40, 70) if rehearsal else (300, 500))
    ]
    steps = 4

    def logits_fn(runner):
        cfg = runner.config
        mesh_, axis = runner._attn_mesh, runner._attn_head_axis
        kv = runner._kv_shard_tree  # keep the cache's layout, as the
        pin = {"out_shardings": (None, kv, kv)} if kv is not None else {}
        return jax.jit(  # runner's own programs do
            lambda p, k, v, t, pos, bt, slots: llama.decode(
                p, cfg, t, pos, k, v, bt, slots,
                mesh=mesh_, attn_head_axis=axis,
            ),
            **pin,
        )

    def run(runner):
        """prefill both prompts through runner.prefill, then `steps` decode
        steps on a fixed token sequence; returns [steps, 2, V] logits and
        the first-token top-logprobs of each prefill."""
        decode_logits = logits_fn(runner)
        nb = max_len // bs
        tables = np.zeros((B, nb), np.int32)
        firsts = []
        for lane, prompt in enumerate(prompts):
            ids = list(range(1 + lane * nb, 1 + (lane + 1) * nb))
            tables[lane] = ids
            out = runner.fetch_sample(
                runner.prefill(prompt, ids, 0.0, 1.0, 0)
            )
            firsts.append(np.asarray(out[3], np.float32))
        out = []
        lens = [len(p) for p in prompts]
        for step in range(steps):
            tokens = np.zeros(B, np.int32)
            positions = np.zeros(B, np.int32)
            slots = np.zeros(B, np.int32)  # idle lanes write null block 0
            for lane in range(len(prompts)):
                pos = lens[lane] + step
                tokens[lane] = 3 + (7 * step + 11 * lane) % 997
                positions[lane] = pos
                slots[lane] = tables[lane, pos // bs] * bs + pos % bs
            logits, runner.k_cache, runner.v_cache = decode_logits(
                runner.params, runner.k_cache, runner.v_cache,
                runner._to_dev(tokens), runner._to_dev(positions),
                runner._to_dev(tables), runner._to_dev(slots),
            )
            out.append(np.asarray(logits, np.float32)[: len(prompts)])
        return np.stack(out), np.stack(firsts)

    (name_a, a), (name_b, b) = [
        (name, run(r)) for name, r in runners.items()
    ]
    scale = float(np.max(np.abs(b[0])))
    per_step = [
        float(np.max(np.abs(a[0][s] - b[0][s]))) / scale for s in range(steps)
    ]
    prefill_diff = float(np.max(np.abs(a[1] - b[1])))
    stats = [d.memory_stats() or {} for d in jax.devices()]
    print(json.dumps({
        "compared": [name_a, name_b],
        "shape": list(a[0].shape),
        "batch": B,
        "table_blocks": max_len // bs,
        "finite": bool(np.isfinite(a[0]).all() and np.isfinite(b[0]).all()),
        "max_abs_logit": round(scale, 4),
        "max_rel_diff": round(max(per_step), 6),
        "max_rel_diff_by_step": [round(x, 6) for x in per_step],
        "prefill_top_logprob_max_abs_diff": round(prefill_diff, 6),
        "argmax_agree": float(
            np.mean(a[0].argmax(-1) == b[0].argmax(-1))
        ),
        "attn_impl": {n: r.attn_impl for n, r in runners.items()},
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
    }), flush=True)


# ----------------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="tiny model on the CPU with interpret kernels; not a pass on "
        "the chip machine",
    )
    ap.add_argument(
        "--four-chips", action="store_true",
        help="the tp=4 path and what it is compared with, and no other phase",
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--logits-child", metavar="MODEL_DIR", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.logits_child:
        logits_child(args)
        return 0
    t0 = time.monotonic()
    try:
        os.makedirs(OUT, exist_ok=True)
        env = child_env(args.cpu_rehearsal, args.four_chips)
        device = probe_device(env)
        emit(
            phase="start", python=sys.version.split()[0], seed=args.seed,
            mode="cpu-rehearsal" if args.cpu_rehearsal else "chip",
            four_chips=args.four_chips, **device,
        )
        want = "cpu" if args.cpu_rehearsal else "tpu"
        check(
            device["platform"] == want,
            f"JAX found {device['platform']!r}, this run needs {want!r}",
        )
        check(
            device["count"] == (4 if args.four_chips else 1),
            f"{device['count']} devices",
        )
        config = TINY if args.cpu_rehearsal else MISTRAL_7B
        model_dir = os.path.join(OUT, "model")
        words = write_model_dir(model_dir, config)
        emit(
            phase="model",
            model="tiny stand-in, widths and depth cut (rehearsal)"
            if args.cpu_rehearsal
            else "mistralai/Mistral-7B-v0.1: no cut in width or depth; "
            "context 4096 of 32768; weights random from --seed, int8 "
            "weight-only (DYN_JAX_QUANTIZE_INT8=1: 7B bf16 weights alone "
            "are 14.5 GB of a 16 GB chip)",
            config={k: config[k] for k in (
                "hidden_size", "intermediate_size", "num_hidden_layers",
                "num_attention_heads", "num_key_value_heads", "vocab_size",
                "sliding_window", "rope_theta",
            )},
            model_dir=model_dir,
        )
        serve_phase(args, env, model_dir, words, device)
        logits_phase(args, env, model_dir)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    emit(phase="done", smoke_seconds_total=round(time.monotonic() - t0, 1))
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": device["platform"], "kind": device["kind"],
            "count": device["count"],
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
