"""The system under test as a child process: the model directory made from
the configuration's file, `python -m dynamo_tpu.run in=http out=jax`, the
wait for readiness, the debug endpoints, the stop.

The handling is `chip_smoke.py`'s (the standing bring-up proof), copied so
that the yardstick lives under the benchmark's own paths. This module never
imports jax: the child is the one process that holds the chip.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time

from cellbench.manifest import hf_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class BenchFailure(Exception):
    """The run cannot give a result; the process exits non-zero."""


def write_model_dir(path: str, config: dict) -> int:
    """config.json plus a word-level tokenizer covering the whole
    vocabulary: ids 0, 1, 2 are <unk>, <s>, </s>, id 3 + i is the word
    `w<i>`. Returns the number of words."""
    from tokenizers import Tokenizer, models, pre_tokenizers

    os.makedirs(path, exist_ok=True)
    hf = hf_config(config)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf, f, indent=1)
    vocab = {"<unk>": 0, "<s>": 1, "</s>": 2}
    words = hf["vocab_size"] - len(vocab)
    for i in range(words):
        vocab[f"w{i}"] = 3 + i
    tok = Tokenizer(models.WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    tok.save(os.path.join(path, "tokenizer.json"))
    return words


def text_of(token_ids: list[int]) -> str:
    return " ".join(f"w{t - 3}" for t in token_ids)


def id_of(word: str) -> int:
    """Token id of one word of the benchmark's tokenizer."""
    special = {"<unk>": 0, "<s>": 1, "</s>": 2}
    word = word.strip()
    if word in special:
        return special[word]
    if word.startswith("w") and word[1:].isdigit():
        return 3 + int(word[1:])
    raise BenchFailure(f"not a word of the benchmark's tokenizer: {word!r}")


def child_env(config: dict, rehearsal: bool) -> dict:
    """A user's shell: no DYN_* variable but those the configuration's file
    sets. The rehearsal adds what makes a CPU take the chip's defaults."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("DYN_")}
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONFAULTHANDLER"] = "1"
    env.update(config["bench"]["server"].get("env", {}))
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
        env["DYN_ATTN_IMPL"] = "pallas_interpret"
        env["DYN_DECODE_HORIZON"] = "4"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    return env


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


def cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir) if not n.startswith("."))
    except OSError:
        return 0


def http_get(port: int, path: str, timeout: float = 30.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def http_json(port: int, path: str, timeout: float = 30.0):
    status, body = http_get(port, path, timeout)
    return status, (json.loads(body) if body else None)


class Server:
    """One `dynamo_tpu.run in=http out=jax` child and what it says of itself."""

    def __init__(self, config: dict, out_dir: str, rehearsal: bool):
        self.config, self.out_dir, self.rehearsal = config, out_dir, rehearsal
        self.env = child_env(config, rehearsal)
        self.model = config["bench"]["name"]
        self.port = free_port()
        self.log = os.path.join(out_dir, "server.log")
        self.proc: subprocess.Popen | None = None
        self.facts: dict = {}

    def start(self) -> None:
        model_dir = os.path.join(self.out_dir, "model")
        self.words = write_model_dir(model_dir, self.config)
        cmd = [
            sys.executable, "-m", "dynamo_tpu.run", "in=http", "out=jax",
            "--model-path", model_dir, "--model-name", self.model,
            "--http-host", "127.0.0.1", "--http-port", str(self.port),
            *self.config["bench"]["server"]["args"],
        ]
        self.cmd = cmd
        with open(self.log, "w") as logf:
            self.proc = subprocess.Popen(
                cmd, env=self.env, cwd=ROOT, stdout=logf,
                stderr=subprocess.STDOUT,
            )

    def wait_ready(self, budget_s: float = 900.0) -> None:
        deadline = time.monotonic() + budget_s
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise BenchFailure(
                    f"server exited rc={self.proc.returncode} before it was "
                    "ready:\n" + tail(self.log)
                )
            try:
                status, _ = http_get(self.port, "/health", timeout=5.0)
                if status == 200:
                    self.facts = self._built_facts()
                    return
            except OSError:
                pass
            time.sleep(0.5)
        raise BenchFailure(f"server not ready after {budget_s:.0f}s:\n" + tail(self.log))

    def _built_facts(self) -> dict:
        marker = "jax engine built: "
        with open(self.log, errors="replace") as f:
            for line in f:
                if marker in line:
                    return json.loads(line[line.index(marker) + len(marker):])
        raise BenchFailure("the server never logged 'jax engine built'")

    def goodput(self) -> dict:
        status, body = http_json(self.port, "/debug/goodput")
        if status != 200 or not body or not body.get("goodput"):
            raise BenchFailure(f"/debug/goodput gave {status}: {body}")
        return body["goodput"]

    def metrics_text(self) -> str:
        status, body = http_get(self.port, "/metrics")
        return body.decode("utf-8", "replace") if status == 200 else ""

    def open_profile(self, seconds: float, out_dir: str) -> dict:
        status, body = http_json(
            self.port, f"/debug/profile?seconds={seconds}&dir={out_dir}"
        )
        if status != 200 or not body or "error" in body:
            raise BenchFailure(f"/debug/profile gave {status}: {body}")
        return body

    def stop(self) -> int | None:
        """SIGINT, as a user's ctrl-c; then wait until it has ended."""
        if self.proc is None:
            return None
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=60.0)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        return self.proc.returncode
