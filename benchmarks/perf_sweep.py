"""Concurrency-sweep perf harness: throughput-vs-latency frontier.

Role-equivalent of the reference's perf harness
(benchmarks/llm/perf.sh — genai-perf sweeps over concurrency against a
running deployment — and plot_pareto.py): drive the real HTTP/SSE serving
process at increasing concurrency, record output tok/s + TTFT + ITL per
level, and emit the Pareto frontier.

    # CPU (tiny random model, exercises the full engine + frontend):
    python -m benchmarks.perf_sweep --json benchmarks/perf_sweep.json

    # real model (TPU when available; any HF dir):
    python -m benchmarks.perf_sweep --model-path /models/llama3-8b \
        --concurrency 1,4,16,64 --max-tokens 150 --prompt-tokens 3000

    # plot the frontier from one or more sweep files:
    python -m benchmarks.plot_pareto benchmarks/perf_sweep.json

Each level reports: output tok/s (aggregate), request throughput,
TTFT p50/p99, ITL p50/p99 — the same axes the reference plots
(throughput/GPU vs ITL; ours is throughput/chip vs ITL).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

from dynamo_tpu.serve import _free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_tiny_model_dir(
    path: str, vocab_words: int = 61, extra_cfg: dict | None = None
) -> None:
    """Self-contained tiny llama HF dir (config + word-level tokenizer) —
    the CPU stand-in for a real checkpoint (weights random-init).
    extra_cfg merges into config.json (e.g. sliding_window for the swa
    preset's Mistral-style tiny model)."""
    os.makedirs(path, exist_ok=True)
    cfg = {
        "model_type": "llama", "vocab_size": 3 + vocab_words,
        "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 10000.0,
        "max_position_embeddings": 512, "rms_norm_eps": 1e-5,
        "eos_token_id": 2, "bos_token_id": 1,
        **(extra_cfg or {}),
    }
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(cfg, f)
    from tokenizers import Tokenizer, models, pre_tokenizers

    vocab = {"<unk>": 0, "<s>": 1, "</s>": 2}
    for i in range(vocab_words):
        vocab[f"w{i}"] = 3 + i
    tok = Tokenizer(models.WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    tok.save(os.path.join(path, "tokenizer.json"))


async def _one(session, url, model, prompt, max_tokens):
    """One streamed request. Returns (ttft, gaps, tokens, status) where
    status is "ok" | "shed" (429 admission control) | "error" — the chaos
    preset drives the server into shedding on purpose, so rejections are a
    counted outcome, not a harness crash."""
    import aiohttp

    body = {
        "model": model, "prompt": prompt, "max_tokens": max_tokens,
        "stream": True, "temperature": 0.7,
        # fixed-length generation (the nvext-style extension block): a
        # throughput sweep must not let random EOS shorten outputs
        "ext": {"ignore_eos": True},
    }
    t0 = time.perf_counter()
    ttft, last, gaps, ntok = None, None, [], 0
    try:
        async with session.post(url, json=body) as resp:
            if resp.status == 429:
                return None, [], 0, "shed"
            resp.raise_for_status()
            async for line in resp.content:
                if not line.startswith(b"data: ") or line.startswith(b"data: [DONE]"):
                    continue
                now = time.perf_counter()
                if ttft is None:
                    ttft = now - t0
                elif last is not None:
                    gaps.append(now - last)
                last = now
                ntok += 1
    except (aiohttp.ClientError, asyncio.TimeoutError):
        return ttft, gaps, max(0, ntok - 1), "error"
    return ttft, gaps, max(0, ntok - 1), "ok"


async def _level(base, model, c, requests, prompt, max_tokens):
    import aiohttp

    url = f"{base}/v1/completions"
    sem = asyncio.Semaphore(c)
    results = []

    async def worker():
        async with sem:
            results.append(await _one(session, url, model, prompt, max_tokens))

    conn = aiohttp.TCPConnector(limit=c + 4)
    async with aiohttp.ClientSession(
        connector=conn, timeout=aiohttp.ClientTimeout(total=600)
    ) as session:
        t0 = time.perf_counter()
        await asyncio.gather(*[worker() for _ in range(requests)])
        wall = time.perf_counter() - t0
    ok = [r for r in results if r[3] == "ok"]
    ttfts = sorted(t for t, _, _, _ in ok if t is not None)
    gaps = sorted(g for _, gs, _, _ in ok for g in gs)
    tokens = sum(n for _, _, n, _ in ok)

    def pct_ms(xs, p, d=2):
        if not xs:
            return None
        return round(xs[min(len(xs) - 1, int(p * len(xs)))] * 1e3, d)

    out = {
        "concurrency": c,
        "requests": requests,
        "output_tokens": tokens,
        "output_tok_per_s": round(tokens / wall, 1),
        "req_per_s": round(len(ok) / wall, 2),
        "ttft_p50_ms": pct_ms(ttfts, 0.50),
        "ttft_p99_ms": pct_ms(ttfts, 0.99),
        "itl_p50_ms": pct_ms(gaps, 0.50, 3),
        "itl_p99_ms": pct_ms(gaps, 0.99, 3),
    }
    shed = sum(1 for r in results if r[3] == "shed")
    failed = sum(1 for r in results if r[3] == "error")
    if shed:
        out["shed"] = shed
    if failed:
        out["failed"] = failed
    return out


async def run_sweep(
    model_path, levels, requests_per_level, prompt_tokens, max_tokens,
    decode_horizon=None, context_length=None, tiny_extra_cfg=None,
    extra_env=None,
):
    own_dir = None
    port = _free_port()
    env = dict(os.environ, PYTHONPATH=REPO, **(extra_env or {}))
    if model_path is None:
        own_dir = tempfile.mkdtemp(prefix="perf-sweep-model-")
        make_tiny_model_dir(own_dir, extra_cfg=tiny_extra_cfg)
        model_path = own_dir
        # tiny-model mode is the CPU harness; a real --model-path keeps
        # the ambient platform
        env["JAX_PLATFORMS"] = "cpu"
    if decode_horizon:
        env["DYN_DECODE_HORIZON"] = str(decode_horizon)
    errlog = tempfile.NamedTemporaryFile(
        mode="w+", suffix=".perf-sweep.log", delete=False
    )
    cmd = [
        sys.executable, "-m", "dynamo_tpu.run",
        "in=http", "out=jax",
        "--model-path", model_path,
        "--model-name", "sweep-model",
        "--http-port", str(port),
        "--max-batch", "16",
    ]
    if context_length:
        cmd += ["--context-length", str(context_length)]
    proc = subprocess.Popen(
        cmd, env=env, stdout=subprocess.DEVNULL, stderr=errlog, cwd="/tmp",
    )
    base = f"http://127.0.0.1:{port}"
    try:
        import aiohttp

        async with aiohttp.ClientSession() as s:
            for _ in range(600):  # first jax compile can take ~40s
                if proc.poll() is not None:
                    errlog.flush()
                    with open(errlog.name) as f:
                        tail = "".join(f.readlines()[-15:])
                    raise RuntimeError(
                        f"server exited rc={proc.returncode}:\n{tail}"
                    )
                try:
                    async with s.get(f"{base}/health") as r:
                        if r.status == 200:
                            break
                except aiohttp.ClientError:
                    pass
                await asyncio.sleep(0.2)
            else:
                raise RuntimeError("server never became healthy")
        prompt = " ".join(f"w{i % 50}" for i in range(prompt_tokens))
        # warmup: trigger prefill+decode compiles outside the measurement
        await _level(base, "sweep-model", 1, 2, prompt, min(8, max_tokens))
        out = []
        for c in levels:
            r = await _level(
                base, "sweep-model", c, max(requests_per_level, c * 2),
                prompt, max_tokens,
            )
            out.append(r)
            print(json.dumps(r), flush=True)
        return out
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


def pareto_frontier(results: list[dict]) -> list[dict]:
    """Levels not dominated on (higher tok/s, lower ITL p50)."""
    out = []
    for r in results:
        dominated = any(
            o is not r
            and o["output_tok_per_s"] >= r["output_tok_per_s"]
            and (o["itl_p50_ms"] or 0) <= (r["itl_p50_ms"] or 0)
            and (
                o["output_tok_per_s"] > r["output_tok_per_s"]
                or (o["itl_p50_ms"] or 0) < (r["itl_p50_ms"] or 0)
            )
            for o in results
        )
        if not dominated:
            out.append(r)
    return sorted(out, key=lambda r: r["concurrency"])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model-path", default=None,
                    help="HF model dir; default = tiny random model")
    ap.add_argument("--concurrency", default="1,2,4,8,16")
    ap.add_argument("--requests-per-level", type=int, default=16)
    ap.add_argument("--prompt-tokens", type=int, default=96)
    ap.add_argument("--max-tokens", type=int, default=32)
    ap.add_argument("--decode-horizon", type=int, default=None)
    ap.add_argument("--context-length", type=int, default=None)
    ap.add_argument(
        "--preset",
        choices=[
            "canonical", "swa", "chaos", "disagg", "trace", "slo",
            "priority", "integrity", "blackout", "planner",
            "tail", "goodput", "sim", "mixed", "prefix", "upgrade",
            "provenance",
        ],
        default=None,
        help="canonical = the reference's genai-perf workload "
        "(examples/llm/benchmarks/README.md:41 — ISL 3000 / OSL 150, "
        "served at max_model_len 3328 = 3000 prompt + 150 output + "
        "slack), so sweeps are directly comparable to its published "
        "throughput/latency curves. swa = sliding-window serving: the "
        "tiny model (or a real --model-path like Mistral) runs with "
        "window << prompt, exercising the windowed flash kernels on the "
        "serving hot path end to end. chaos = the sweep with fault "
        "injection ON (DYN_FAULT dispatch delays) and a bounded admission "
        "watermark, so the curve shows shed counts and the TTFT of "
        "ADMITTED requests under overload instead of an unbounded queue. "
        "disagg = delegates to benchmarks.disagg_stream_bench (streamed "
        "vs monolithic P/D TTFT over a simulated wire; banked artifact "
        "benchmarks/disagg_stream.json). trace = delegates to "
        "benchmarks.trace_overhead_bench (token throughput DYN_TRACE off "
        "vs on; banked artifact benchmarks/trace_overhead.json). "
        "slo = delegates to benchmarks.slo_overhead_bench (always-on "
        "phase histograms + DYN_TRACE=auto flight recorder vs the PR 5 "
        "disabled baseline; banked artifact benchmarks/slo_overhead.json). "
        "priority = delegates to benchmarks.priority_sweep (4x-overload "
        "1:4 interactive:bulk mix, class-blind vs QoS: per-class TTFT, "
        "shed/preempt counts, brownout timeline; banked artifact "
        "benchmarks/priority_sweep.json). "
        "integrity = delegates to benchmarks.integrity_sweep (checksum "
        "codec overhead, streamed-disagg TTFT checksums on vs off with "
        "a <=3% bar, and the corrupt_kv/zombie fault proof; banked "
        "artifact benchmarks/integrity_sweep.json). "
        "blackout = delegates to benchmarks.blackout_sweep (throughput/"
        "TTFT through a mid-traffic control-plane blackout vs steady "
        "state — zero errors, zero divergence — plus warm-restart TTFT "
        "vs cold on a repeated-prefix workload; banked artifact "
        "benchmarks/blackout_sweep.json). "
        "planner = delegates to benchmarks.planner_sweep (closed-loop "
        "planner over a mocker fleet on diurnal + flash-crowd traces: "
        "SLO attainment vs replica-seconds against a static max fleet, "
        "plus the chaos wave — frozen through a blackout, healed within "
        "2 intervals, zero planner/brownout oscillation; banked "
        "artifact benchmarks/planner_sweep.json). "
        "tail = tail-tolerance sweep (one 5x gray straggler in a "
        "4-worker mocker fleet: hedged-vs-unhedged p99 TTFT, ejection "
        "count, hedge overhead accounting, gray-flap hysteresis; "
        "banked artifact benchmarks/tail_sweep.json). "
        "goodput = delegates to benchmarks.goodput_bench (token-waste "
        "taxonomy reconciled against client-side ground truth <=1%, "
        "spec_rejected vs the spec plane's own counters, DYN_GOODPUT "
        "on/off overhead <=2%, and a forced shape-bucket miss producing "
        "exactly one labelled recompile increment; banked artifact "
        "benchmarks/goodput_sweep.json). "
        "sim = delegates to tools.sim_sweep (N-seed deterministic "
        "virtual-clock chaos sweep: the real fleet through every fault "
        "class with always-on invariant checkers; failing seeds bank "
        "ddmin-shrunk replay artifacts; banked artifact "
        "benchmarks/sim_sweep.json). "
        "mixed = delegates to benchmarks.mixed_load_sweep (unified mixed "
        "prefill+decode device steps vs the phase-separated scheduler on "
        "the same workload: phase-bubble fraction, TTFT/ITL, dispatch "
        "count, token-identity, zero steady-state recompiles; banked "
        "artifact benchmarks/mixed_load_sweep.json). "
        "prefix = delegates to benchmarks.prefix_sweep (fleet prefix "
        "cache A/B on a Zipf multi-tenant chat trace with thousands of "
        "distinct system prompts: KV-aware routing alone vs + peer-pull "
        "prefix reuse — prefill tokens/request, p50 TTFT, token-identity, "
        "pulled blocks by outcome with deterministic pull failures; "
        "banked artifact benchmarks/prefix_sweep.json). "
        "upgrade = delegates to benchmarks.upgrade_sweep (zero-downtime "
        "rolling upgrade on the virtual-clock sim fleet: live-KV-handoff "
        "rollout vs cold rolling restart — successor prefill recompute "
        "ratio, rollout-window p50 TTFT vs steady state, zero dropped "
        "streams — plus the forced successor-crash halt+rollback drill; "
        "banked artifact benchmarks/upgrade_sweep.json, gated by "
        "tools/upgrade_gate.py). "
        "provenance = delegates to benchmarks.provenance_bench (decision-"
        "ledger overhead: DYN_DECISIONS on/off throughput delta <=2%, "
        "ns/decision on the enabled record path, disabled fast-path "
        "ns/op, and decision completeness 1.0 over the four workload "
        "kinds; banked artifact benchmarks/provenance_sweep.json)",
    )
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    if args.preset == "disagg":
        # the disagg data-plane sweep has its own harness (two engines +
        # throttled fabric instead of an HTTP frontend); keep one entry
        # point so `perf_sweep --preset X` covers every banked curve
        from benchmarks import disagg_stream_bench

        disagg_stream_bench.main(
            ["--json", args.json or "benchmarks/disagg_stream.json"]
        )
        return
    if args.preset == "trace":
        # tracer-overhead sweep runs on the mocker directly (no HTTP
        # frontend): disabled-mode throughput must match the pre-tracing
        # baseline, enabled-mode cost is banked alongside
        from benchmarks import trace_overhead_bench

        trace_overhead_bench.main(
            ["--json", args.json or "benchmarks/trace_overhead.json"]
        )
        return
    if args.preset == "priority":
        # QoS sweep has its own two-run harness (class-blind baseline vs
        # priority-labelled at identical load) — one entry point for every
        # banked curve stays `perf_sweep --preset X`
        from benchmarks import priority_sweep

        priority_sweep.main(
            ["--json", args.json or "benchmarks/priority_sweep.json"]
        )
        return
    if args.preset == "integrity":
        # integrity-plane sweep has its own harness (codec microbench +
        # streamed-disagg A/B + fault proof) — one entry point for every
        # banked curve stays `perf_sweep --preset X`
        from benchmarks import integrity_sweep

        integrity_sweep.main(
            ["--json", args.json or "benchmarks/integrity_sweep.json"]
        )
        return
    if args.preset == "planner":
        # closed-loop planner sweep runs on the mocker fleet directly
        # (no HTTP frontend) — one entry point for every banked curve
        # stays `perf_sweep --preset X`
        from benchmarks import planner_sweep

        planner_sweep.main(
            ["--json", args.json or "benchmarks/planner_sweep.json"]
        )
        return
    if args.preset == "blackout":
        # control-plane blackout sweep has its own harness (mocker disagg
        # A/B + tiny-engine warm-restart TTFT) — one entry point for
        # every banked curve stays `perf_sweep --preset X`
        from benchmarks import blackout_sweep

        blackout_sweep.main(
            ["--json", args.json or "benchmarks/blackout_sweep.json"]
        )
        return
    if args.preset == "tail":
        # tail-tolerance sweep runs on the mocker fleet directly (hedged
        # vs unhedged p99 TTFT against one 5x gray straggler + ejection
        # and gray-flap hysteresis proof) — one entry point for every
        # banked curve stays `perf_sweep --preset X`
        from benchmarks import tail_sweep

        tail_sweep.main(
            ["--json", args.json or "benchmarks/tail_sweep.json"]
        )
        return
    if args.preset == "goodput":
        # goodput-ledger sweep runs on the mocker + tiny spec engine
        # directly (waste reconciliation, overhead A/B, recompile
        # forensics) — one entry point for every banked curve stays
        # `perf_sweep --preset X`
        from benchmarks import goodput_bench

        goodput_bench.main(
            ["--json", args.json or "benchmarks/goodput_sweep.json"]
        )
        return
    if args.preset == "sim":
        # deterministic-simulation sweep runs the whole fleet on a
        # virtual clock (no HTTP frontend, no wall-clock sleeps) — one
        # entry point for every banked curve stays `perf_sweep --preset X`
        from tools import sim_sweep

        raise SystemExit(sim_sweep.main(
            ["--json", args.json or "benchmarks/sim_sweep.json"]
        ))
    if args.preset == "mixed":
        # mixed-step A/B runs two in-proc tiny-llama engines directly
        # (no HTTP frontend) — one entry point for every banked curve
        # stays `perf_sweep --preset X`
        from benchmarks import mixed_load_sweep

        mixed_load_sweep.main(
            ["--json", args.json or "benchmarks/mixed_load_sweep.json"]
        )
        return
    if args.preset == "upgrade":
        # rolling-upgrade A/B runs the whole fleet on a virtual clock
        # (no HTTP frontend, no wall-clock sleeps) — one entry point for
        # every banked curve stays `perf_sweep --preset X`
        from benchmarks import upgrade_sweep

        raise SystemExit(upgrade_sweep.main(
            ["--json", args.json or "benchmarks/upgrade_sweep.json"]
        ))
    if args.preset == "prefix":
        # fleet-prefix-cache A/B runs on the mocker fleet + real KvRouter
        # directly (no HTTP frontend) — one entry point for every banked
        # curve stays `perf_sweep --preset X`
        from benchmarks import prefix_sweep

        prefix_sweep.main(
            ["--json", args.json or "benchmarks/prefix_sweep.json"]
        )
        return
    if args.preset == "provenance":
        # decision-ledger overhead sweep runs on the mocker + real
        # admission/QoS surfaces directly (no HTTP frontend) — one entry
        # point for every banked curve stays `perf_sweep --preset X`
        from benchmarks import provenance_bench

        provenance_bench.main(
            ["--json", args.json or "benchmarks/provenance_sweep.json"]
        )
        return
    if args.preset == "slo":
        # SLO-plane overhead sweep runs on the mocker directly: always-on
        # histogram recording must stay within a few percent of the PR 5
        # disabled baseline, auto-mode cost banked alongside
        from benchmarks import slo_overhead_bench

        slo_overhead_bench.main(
            ["--json", args.json or "benchmarks/slo_overhead.json"]
        )
        return
    tiny_extra_cfg = None
    extra_env = None
    if args.preset == "canonical":
        args.prompt_tokens = 3000
        args.max_tokens = 150
        if args.context_length is None:
            args.context_length = 3328
    elif args.preset == "swa":
        # long-ish prompt over a small window: the regime where windowed
        # decode traffic (O(window)) separates from the dense gather
        # (O(context)); Mistral-style full-depth sliding on the tiny model
        args.prompt_tokens = max(args.prompt_tokens, 192)
        tiny_extra_cfg = {"model_type": "mistral", "sliding_window": 64}
    elif args.preset == "chaos":
        # overload + faults: concurrency sweeps PAST the admission cap, a
        # periodic dispatch stall jitters the engine loop, and every
        # request carries a deadline — the lifeguard must keep admitted
        # TTFT bounded and convert the excess into counted 429s
        extra_env = {
            "DYN_FAULT": "delay_dispatch=0.05,every=7",
            "DYN_ADMISSION_MAX_INFLIGHT": os.environ.get(
                "DYN_ADMISSION_MAX_INFLIGHT", "12"
            ),
            "DYN_DEFAULT_DEADLINE_MS": os.environ.get(
                "DYN_DEFAULT_DEADLINE_MS", "120000"
            ),
        }
        if args.concurrency == "1,2,4,8,16":
            args.concurrency = "4,8,16,32,48"
    levels = [int(x) for x in args.concurrency.split(",")]
    results = asyncio.run(
        run_sweep(
            args.model_path, levels, args.requests_per_level,
            args.prompt_tokens, args.max_tokens,
            decode_horizon=args.decode_horizon,
            context_length=args.context_length,
            tiny_extra_cfg=tiny_extra_cfg,
            extra_env=extra_env,
        )
    )
    doc = {
        "bench": "perf_sweep",
        "model": args.model_path or "tiny-random",
        "preset": args.preset,
        "prompt_tokens": args.prompt_tokens,
        "max_tokens": args.max_tokens,
        "context_length": args.context_length,
        "results": results,
        "pareto": pareto_frontier(results),
    }
    print(json.dumps({"pareto": [r["concurrency"] for r in doc["pareto"]]}))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")


if __name__ == "__main__":
    main()
