"""Speculative-decoding smoke bench: spec-on vs spec-off on deterministic
CPU traces (counts of accepted drafts and dispatches, not device speed:
`DYN_SPEC_K` has no reading on the chip yet, ROADMAP D1).

Two workloads, both greedy and fully deterministic:

  * repetitive — prompts are a short random phrase tiled out to the ISL,
    the regime prompt-lookup drafting targets (quoted code, templated
    phrasing, multi-turn restatement in ShareGPT-like traffic). Greedy
    decoding on a looping prompt locks into loops too, so the drafter's
    n-gram hits keep paying all the way through the OSL.
  * random — i.i.d. uniform prompts: the adversarial case. The drafter
    should mostly decline to draft (min_n-gram gate) and the verify pass
    should cost ~nothing vs plain decode.

Emits one JSON doc (tok/s on/off per workload, speedup, acceptance rate)
and optionally writes it to --json (benchmarks/spec_smoke.json is the
committed artifact).

    JAX_PLATFORMS=cpu python -m benchmarks.spec_smoke \
        --json benchmarks/spec_smoke.json
"""

from __future__ import annotations

import argparse
import asyncio
import json
import time

import numpy as np


def make_workload(
    kind: str, n: int, vocab: int, isl: int, osl: int, seed: int = 0
) -> list[tuple[list[int], int]]:
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        if kind == "repetitive":
            phrase = rng.integers(1, vocab, size=8).tolist()
            prompt = (phrase * (isl // len(phrase) + 1))[:isl]
        else:
            prompt = rng.integers(1, vocab, size=isl).tolist()
        out.append((prompt, osl))
    return out


def build_engine(spec_k: int, max_batch: int = 4, ngram_min: int = 3):
    import jax

    from dynamo_tpu.engine.jax_engine.engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner
    from dynamo_tpu.models import llama as L

    cfg = L.LlamaConfig.tiny(vocab_size=256)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    runner = ModelRunner(
        cfg, params,
        num_blocks=512, block_size=16,
        max_batch=max_batch, max_model_len=512,
        prefill_buckets=[128, 512], prefill_chunk_tokens=128,
    )
    engine = JaxEngine(
        runner,
        JaxEngineConfig(
            max_batch=max_batch, block_size=16, num_blocks=512,
            max_model_len=512, spec_k=spec_k, spec_ngram_min=ngram_min,
        ),
    )
    return engine, cfg


async def run_one(engine, workload, concurrency: int) -> dict:
    from dynamo_tpu.pipeline.context import Context
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    sem = asyncio.Semaphore(concurrency)
    tokens_done = 0

    async def one(prompt, osl):
        nonlocal tokens_done
        async with sem:
            req = PreprocessedRequest(
                token_ids=prompt,
                sampling=SamplingOptions(greedy=True),
                stop=StopConditions(max_tokens=osl, ignore_eos=True),
            )
            async for out in engine.generate(req, Context()):
                tokens_done += len(out.token_ids)

    # warmup (compiles) outside the measurement
    await one(*workload[0])
    tokens_done = 0
    t0 = time.perf_counter()
    await asyncio.gather(*[one(p, o) for p, o in workload[1:]])
    wall = time.perf_counter() - t0
    s = engine.stats
    return {
        "output_tokens": tokens_done,
        "wall_s": round(wall, 3),
        "tok_s": round(tokens_done / wall, 1),
        "drafts": s.num_drafts,
        "draft_tokens": s.num_draft_tokens,
        "accepted_tokens": s.num_accepted_tokens,
        "acceptance_rate": round(s.draft_acceptance_rate, 4),
    }


async def run(args) -> dict:
    doc: dict = {
        "bench": "spec_smoke",
        "spec_k": args.spec_k,
        "requests": args.requests,
        "isl": args.isl,
        "osl": args.osl,
        "repeats": args.repeats,
    }
    for kind in ("repetitive", "random"):
        wl = make_workload(
            kind, args.requests, 256, args.isl, args.osl, seed=args.seed
        )
        # Interleave off/on repeats and take medians: single-core CI boxes
        # jitter +-20% run to run, far above the effect under test — a
        # single A/B pair would regularly report speedups in either
        # direction on IDENTICAL code.
        samples: dict[str, list[dict]] = {"off": [], "on": []}
        for _ in range(args.repeats):
            for label, k in (("off", 0), ("on", args.spec_k)):
                engine, _ = build_engine(
                    k, max_batch=args.max_batch, ngram_min=args.ngram_min,
                )
                try:
                    samples[label].append(
                        await run_one(engine, wl, args.concurrency)
                    )
                finally:
                    await engine.close()
        row: dict = {}
        import statistics

        for label in ("off", "on"):
            med = statistics.median(s["tok_s"] for s in samples[label])
            best = max(samples[label], key=lambda s: s["tok_s"])
            row[label] = dict(best, tok_s_median=round(med, 1))
        row["speedup"] = round(
            row["on"]["tok_s_median"] / max(1e-9, row["off"]["tok_s_median"]),
            3,
        )
        doc[kind] = row
        print(json.dumps({kind: row}), flush=True)
    return doc


def main() -> None:
    ap = argparse.ArgumentParser()
    # Defaults tuned on the 1-core CI box (the adversarial regime for
    # speculation: FLOP-bound, no weight-read to amortize): batch 4 keeps
    # draft coverage per dispatch high, n-gram >= 3 keeps drafts precise,
    # OSL 192 lets the greedy loops the drafter feeds on dominate.
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--concurrency", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--ngram-min", type=int, default=3)
    ap.add_argument("--isl", type=int, default=96)
    ap.add_argument("--osl", type=int, default=192)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    doc = asyncio.run(run(args))
    print(json.dumps(doc))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")


if __name__ == "__main__":
    main()
