#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python cellbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Writes the model directory from the configuration's file, starts
`python -m dynamo_tpu.run in=http out=jax` as a child (this process never
touches the chip) and the plain reference as another (on the host's CPU),
waits for ready, sends the check's probes and warms the cell's own programs,
ramps the traffic, measures for `--seconds`, stops the children, and prints
as its last line the one JSON object of the contract (`correct`,
`attempted`, `failed`, `metrics`, `device`, and `breakdown` in a traced
run). Earlier lines are one JSON object each, for a reader. Files go to
`cellbench_out/<workload>/` in the checkout.

No chip, too few chips, a missing program: the run fails with a non-zero
code and prints no result. It never falls back to a CPU. `--cpu-rehearsal`
drives the same code on a toy model on the CPU so that it cannot rot where
there is no chip; its line says `"platform": "cpu"` and `"rehearsal": true`
and is no result. `--describe` prints what the cell resolves to and runs
nothing.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import subprocess
import sys
import time

T_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from cellbench import client, clientmath, manifest  # noqa: E402
from cellbench.server import (  # noqa: E402
    BenchFailure, Server, cache_entries, tail,
)


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


def cache_dir() -> str:
    """Where the program keeps its compile cache: `runtime.config.jax_cache_dir`
    (a fixed path under the checkout unless JAX_COMPILATION_CACHE_DIR is set).
    Asked of the program, which imports no jax for it."""
    from dynamo_tpu.runtime.config import jax_cache_dir

    return jax_cache_dir()


# ------------------------------------------------------------- the reference


class Reference:
    """`cellbench/refcheck.py` as a child on the host's CPU."""

    def __init__(self, config_file: str, out_dir: str):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        self.log = os.path.join(out_dir, "reference.log")
        self._logf = open(self.log, "w")
        self.ready: dict | None = None
        self.asked = 0
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "cellbench", "refcheck.py"), config_file],
            env=env, cwd=ROOT, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self._logf, text=True,
        )

    def _line(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise BenchFailure(
                f"the reference ended (rc={self.proc.poll()}):\n" + tail(self.log)
            )
        return json.loads(line)

    def ask(self, question: dict) -> None:
        """Queue a question; the child reads it once its weights are made."""
        self.proc.stdin.write(json.dumps(question) + "\n")
        self.proc.stdin.flush()
        self.asked += 1

    def verdict(self) -> dict:
        """The answer to the last question asked: the comparison over every
        probe so far. Blocks until the child has answered them all."""
        if self.ready is None:
            self.ready = self._line()
        out = None
        while self.asked:
            out = self._line()
            self.asked -= 1
            if "error" in out:
                raise BenchFailure(f"the reference failed: {out['error']}")
        if out is None:
            raise BenchFailure("the reference was asked nothing")
        return out

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.stdin.close()
            try:
                self.proc.wait(timeout=3)  # it holds no state worth waiting for
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._logf.close()


def probe_requests(config: dict, seed: int, group: int) -> list[dict]:
    """One group of the check's sequences: token ids from the seed, lengths
    from the configuration's file."""
    import random

    spec = config["bench"]["check"]["probes"][group]
    rng = random.Random((int(seed) ^ 0x5EED) + 7919 * group)
    vocab = config["vocab_size"]
    return [
        {
            "index": i, "due_s": 0.0,
            "token_ids": [rng.randrange(3, vocab) for _ in range(spec["prompt_tokens"])],
            "output_tokens": spec["output_tokens"],
        }
        for i in range(spec["count"])
    ]


def probe_question(requests: list[dict], records: list[dict], lower=()) -> dict:
    probes = []
    for req, rec in zip(requests, records):
        if rec["error"] or not rec.get("top"):
            raise BenchFailure(f"a probe stream failed: {rec['error']}")
        ids, top_ids, top_lps = client.parse_top(rec)
        n = len(req["token_ids"])
        probes.append({
            "tokens": req["token_ids"] + ids,
            # logits at row n - 1 + i predict generated token i
            "rows": [n - 1 + i for i in range(len(ids))],
            "top_ids": top_ids, "top_lps": top_lps,
        })
    return {"probes": probes, "lower": list(lower)}


# ----------------------------------------------------------------- the run


def warm_requests(phase: dict, vocab: int, rng) -> list[dict]:
    return [
        {
            "index": i, "due_s": float(r.get("delay_s", 0.0)),
            "token_ids": [rng.randrange(3, vocab) for _ in range(r["prompt_tokens"])],
            "output_tokens": r["output_tokens"],
        }
        for i, r in enumerate(phase["requests"])
    ]


async def warm_up(server: Server, cell, seed: int, reference) -> dict:
    """The mix's warm-up phases, in order. A phase is one of:

    * `{"check_group": i}`: group i of the configuration's probes, sent at
      once with log-probs asked for; the reference is asked about them and
      answers in its own time;
    * `{"requests": [...]}`: streams of the given lengths, waited for; with
      `"linger": true` they are not waited for (after `linger_s` the next
      phase starts), so they keep lanes live under what follows, the ramp's
      first long prompt included;
    * `{"await_reference": true}`: wait for the reference's verdict.

    Returns the verdict and the lingering tasks."""
    import random

    config, mix = cell.config, cell.mix
    check = config["bench"]["check"]
    rng = random.Random(int(seed) ^ 0xA11)
    lingering = []
    verdict = None
    for phase in mix["warmup"]:
        if phase.get("await_reference"):
            verdict = await asyncio.to_thread(reference.verdict)
            continue
        if "check_group" in phase:
            probes = probe_requests(config, seed, phase["check_group"])
            recs = await client.offer(
                server.port, server.model, probes, time.monotonic(), None, 0.0,
                top_logprobs=check["top_logprobs"],
            )
            await asyncio.to_thread(reference.ask, probe_question(probes, recs))
            continue
        reqs = warm_requests(phase, config["vocab_size"], rng)
        task = asyncio.create_task(client.offer(
            server.port, server.model, reqs, time.monotonic(), None,
            mix["temperature"],
        ))
        if phase.get("linger"):
            lingering.append(task)
            await asyncio.sleep(float(phase.get("linger_s", 0.0)))
            continue
        for rec in await task:
            if rec["error"]:
                raise BenchFailure(f"a warm-up stream failed: {rec['error']}")
    if verdict is None:
        verdict = await asyncio.to_thread(reference.verdict)
    return {"verdict": verdict, "lingering": lingering}


async def measure(server: Server, cell, seed: int, seconds: float, trace: bool,
                  out_dir: str, jax_cache: str) -> dict:
    """Ramp, then the window. Readings of the engine's ledger and of the
    compile cache bracket the window on the client's clock."""
    mix = cell.mix
    gen = cell.generator()
    requests = gen.generate(mix, seed, seconds, cell.config["vocab_size"])
    t0 = time.monotonic() + 0.2
    w0 = t0 + float(mix["ramp_s"])
    w1 = w0 + seconds
    state: dict = {"opened": False, "profile": None}
    profile_dir = os.path.join(out_dir, "profile")

    async def tick(now: float, _records) -> None:
        if not state["opened"] and now >= w0:
            state["opened"] = True
            state["ledger0"] = await asyncio.to_thread(server.goodput)
            state["prom0"] = await asyncio.to_thread(server.metrics_text)
            state["cache0"] = await asyncio.to_thread(cache_entries, jax_cache)
            state["setup_s"] = w0 - T_START
        if trace and state["profile"] is None and now >= w0 + float(mix["trace_offset_s"]):
            state["profile"] = await asyncio.to_thread(
                server.open_profile, float(mix["trace_seconds"]), profile_dir
            )
            state["profile_at"] = now

    records = await client.offer(
        server.port, server.model, requests, t0, w1, mix["temperature"], on_tick=tick,
    )
    state["ledger1"] = server.goodput()
    state["prom1"] = server.metrics_text()
    state["cache1"] = cache_entries(jax_cache)
    state.update(records=records, w0=w0, w1=w1, n_requests=len(requests))
    return state


def wait_for_trace(profile_dir: str, budget_s: float = 240.0) -> None:
    import glob

    deadline = time.monotonic() + budget_s
    size = -1
    while time.monotonic() < deadline:
        found = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True)
        now = os.path.getsize(found[0]) if found else -1
        if found and now == size and now > 0:
            return
        size = now
        time.sleep(1.0)


def result_line(cell, server: Server, state: dict, verdict: dict, trace: bool,
                out_dir: str, rehearsal: bool) -> dict:
    records, w0, w1 = state["records"], state["w0"], state["w1"]
    summary = clientmath.summarise(records, w0, w1)
    # every stream's timestamps, counted from the window's opening: what a
    # reader needs to see why a statistic moved between two runs
    with open(os.path.join(out_dir, "requests.json"), "w") as f:
        json.dump({"window_s": w1 - w0, "requests": [
            {**{k: r[k] for k in ("index", "prompt_tokens", "output_tokens", "done", "error")},
             "due": r["due"] - w0, "sent": None if r["sent"] is None else r["sent"] - w0,
             "tokens": [round(t - w0, 4) for t in r["tokens"]]}
            for r in records
        ]}, f)
    ctx = {
        "client": summary, "clock": {"setup_s": state["setup_s"]},
        "ledger0": state["ledger0"], "ledger1": state["ledger1"],
        "prom0": state["prom0"], "prom1": state["prom1"],
        "facts": server.facts, "config": cell.config, "mix": cell.mix,
        "trace": None, "notes": {},
    }
    device = {
        "platform": server.facts["platform"], "kind": server.facts["device_kind"],
        "count": server.facts["device_count"],
        # as the child reported it when the engine was built: weights and the
        # whole cache pool; a step's temporaries come and go above it
        "memory_peak_bytes": max(
            (b or 0) for b in server.facts["peak_bytes_in_use"]
        ),
    }
    breakdown = None
    if trace:
        os.environ["JAX_PLATFORMS"] = "cpu"  # the children have ended
        from cellbench import trace_reduce as tr

        raw = tr.load_xplane(tr.find_xplane(state["profile"]["profile_dir"]))
        red = tr.reduce_device(raw)
        ctx["trace"] = red
        if red["planes"]:
            # a second and a half of it, for a reader and for the tests
            lo = red["planes"][0]["span"][0] + 1.0e9
            tr.save(tr.slice_trace(raw, lo, lo + 1.5e9), os.path.join(out_dir, "trace_slice.json.gz"))
        at = state["profile_at"]
        summary["live"] = clientmath.live_lanes_context(
            records, at, at + float(cell.mix["trace_seconds"])
        )
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        breakdown = {
            "device_ops": tr.top_ops(red), "idle_gaps": tr.idle_gaps(red, raw),
        }
        if not rehearsal and red["busy_s"] <= 0:
            raise BenchFailure("the trace holds no device operation")
    metrics = {}
    for m in cell.metrics("per_layer" if trace else "end_to_end"):
        value = manifest.reader(m["reader"]).read(ctx, m["params"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    attempted, failed = clientmath.attempted_failed(records, w0, w1)
    broken = [r for r in records if r["error"]]
    tol = cell.config["bench"]["check"]["tolerance_rms_rel"]
    served = verdict["served"]
    compiled = state["cache1"] - state["cache0"]
    new_labels = sorted(
        set(state["ledger1"]["compile_s_by_label"]) - set(state["ledger0"]["compile_s_by_label"])
    )
    emit(
        phase="check", compared="served top log-probs against the float32 reference",
        rms_rel=served["rms_rel"], limit=tol, max_rel=served["max_rel"],
        positions=served["positions"], values=served["values"],
        reference_seconds=verdict["seconds"],
        streams_broken=len(broken), streams_broken_limit=0,
        first_errors=[r["error"] for r in broken[:3]],
    )
    emit(
        phase="window", seconds=w1 - w0, requests_offered=state["n_requests"],
        compile_cache_entries_added_in_window=compiled,
        labels_first_dispatched_in_window=new_labels,
        labels_first_dispatched_in_ramp=sorted(
            set(state["ledger0"]["compile_s_by_label"]) - set(state["warmed"])
        ),
        recompiles=state["ledger1"]["recompiles"],
        warm_up_fault=bool(compiled or new_labels),
        streams_waiting_for_a_first_token_at_the_end=sum(
            1 for r in records if r["sent"] is not None and not r["tokens"] and not r["error"]
        ),
        client=summary, notes=ctx["notes"],
        dispatches={
            k: v["count"] - state["ledger0"]["steps_by_label"].get(k, {"count": 0})["count"]
            for k, v in state["ledger1"]["steps_by_label"].items()
        },
    )
    line = {
        "correct": bool(served["rms_rel"] <= tol and not broken),
        "attempted": attempted, "failed": failed, "metrics": metrics,
        "device": device,
    }
    if breakdown:
        line["breakdown"] = breakdown
    if rehearsal:
        line["rehearsal"] = True
    return line


def describe(cell) -> dict:
    """What the cell resolves to; imports every module it names."""
    import importlib

    gen = cell.generator()
    bench = cell.config["bench"]
    importlib.import_module(f"cellbench.counts.{bench['counts']}")
    metrics = {}
    for group in ("end_to_end", "per_layer"):
        for m in cell.metrics(group):
            manifest.reader(m["reader"])
            metrics[m["name"]] = {"group": group, "reader": m["reader"], "unit": m["unit"]}
    return {
        "workload": cell.name, "chips": cell.chips, "config": bench["name"],
        "config_file": os.path.relpath(cell.config_file, cell.root),
        "traffic": cell.entry["traffic"], "generator": cell.mix["generator"],
        "reference": bench["reference"], "counts": bench["counts"],
        "requests_at_10s": len(gen.generate(cell.mix, 1, 10.0, cell.config["vocab_size"])),
        "metrics": metrics,
    }


async def run(args, cell, out_dir: str) -> dict:
    rehearsal = args.cpu_rehearsal
    jax_cache = cache_dir()
    server = Server(cell.config, out_dir, rehearsal)
    if not rehearsal:
        # the child must find the chip or fail at start-up: no CPU fallback
        server.env["JAX_PLATFORMS"] = "tpu"
    reference = Reference(cell.config_file, out_dir)
    lingering = []
    state = None
    try:
        server.start()
        emit(phase="start", workload=cell.name, seed=args.seed, cmd=" ".join(server.cmd[1:]),
             cache_dir=jax_cache, cache_entries=cache_entries(jax_cache))
        await asyncio.to_thread(server.wait_ready)
        facts = server.facts
        emit(phase="ready", seconds=time.monotonic() - T_START, engine=facts)
        want = "cpu" if rehearsal else "tpu"
        if facts["platform"] != want or facts["device_count"] != cell.chips:
            raise BenchFailure(
                f"the server holds {facts['device_count']} x {facts['platform']}; "
                f"the cell needs {cell.chips} x {want}"
            )
        if facts["cache_dir"] != jax_cache:
            raise BenchFailure(f"server caches in {facts['cache_dir']}, not {jax_cache}")
        warm = await warm_up(server, cell, args.seed, reference)
        lingering = warm["lingering"]
        warmed = server.goodput()["compile_s_by_label"]
        emit(phase="warm", seconds=time.monotonic() - T_START,
             reference_weights_s=reference.ready["weights_s"],
             first_dispatch_seconds=warmed)
        state = await measure(
            server, cell, args.seed, float(args.seconds), bool(args.trace),
            out_dir, jax_cache,
        )
        state["warmed"] = sorted(warmed)
    except BaseException:
        died = server.proc.poll() if server.proc else None
        print(f"cellbench: the run failed; server rc so far: {died}; its log ends:\n"
              + tail(server.log, 60), file=sys.stderr, flush=True)
        raise
    finally:
        for t in lingering:
            t.cancel()
        await asyncio.gather(*lingering, return_exceptions=True)
        if state and state.get("profile"):
            # the profiler's timer thread closes the window and writes the
            # file, which takes longer than the window; it dies with the
            # server, so wait for the file first
            await asyncio.to_thread(wait_for_trace, state["profile"]["profile_dir"])
        rc = await asyncio.to_thread(server.stop)
        await asyncio.to_thread(reference.stop)
    emit(phase="stopped", server_rc=rc)
    return result_line(cell, server, state, warm["verdict"], bool(args.trace),
                       out_dir, rehearsal)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="toy model on the CPU; no result")
    ap.add_argument("--describe", action="store_true",
                    help="print what the cell resolves to and run nothing")
    args = ap.parse_args()
    try:
        cell = manifest.Cell(args.workload)
        if args.describe:
            print(json.dumps(describe(cell)), flush=True)
            return 0
        if args.seconds is None:
            args.seconds = float(cell.bench["run_seconds"])
        if args.cpu_rehearsal:
            cell.rehearse()
        out_dir = os.path.join(ROOT, "cellbench_out", cell.name)
        os.makedirs(out_dir, exist_ok=True)
        line = asyncio.run(run(args, cell, out_dir))
    except (BenchFailure, KeyError, FileNotFoundError, ModuleNotFoundError) as e:
        print(f"cellbench FAILED: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
