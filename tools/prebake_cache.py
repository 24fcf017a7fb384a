"""AOT pre-bake: compile the serve-shape program set into the persistent
XLA cache BEFORE serving traffic.

The banked TPU capture (`BENCH_TPU_LOCAL.json`) spends 46.6 s of its
window compiling the engine's programs on first touch. Those compiles are
deterministic functions of (model config, serve shape, jax/libtpu
version) — so bake them at container-BUILD time instead:

    JAX_COMPILATION_CACHE_DIR=/opt/dynamo/jax_cache \
        python -m tools.prebake_cache --model-path /models/llama3-8b \
        --max-batch 64 --decode-horizon 4

and ship the populated cache directory in the image (see README
"Pre-baking the compile cache"). On boot, every program the engine
dispatches is a cache HIT: prefill per bucket, packed + chunked prefill,
single-step decode (plain / eos-masked), the unrolled decode horizon, and
spec-verify when --spec-k is set. `--tiny` pre-bakes the CPU test model
(used by the smoke test and CI).

The tool drives real dispatches through ModelRunner with null inputs, so
it exercises exactly the (shape, dtype, donation) signatures serving
uses — including DYN_KV_DTYPE / DYN_FUSED_DECODE / DYN_JAX_QUANTIZE_INT8,
which change the compiled programs and are read from the environment the
same way factory.build_jax_engine reads them.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from dynamo_tpu.telemetry.goodput import write_prebake_manifest


def _build_runner(args):
    import jax

    from dynamo_tpu.engine.jax_engine.factory import (
        collective_overlap_from_env,
        fused_decode_from_env,
        kv_dtype_from_env,
    )
    from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner
    from dynamo_tpu.models import llama

    quantize = os.environ.get("DYN_JAX_QUANTIZE_INT8", "0") in ("1", "true")
    if args.tiny:
        config = llama.LlamaConfig.tiny()
        params = llama.init_params(
            config, jax.random.PRNGKey(0), quantize=quantize
        )
        max_len = args.context_length or 512
    else:
        from dynamo_tpu.engine.jax_engine.weights import load_or_init_params

        config = llama.LlamaConfig.from_model_dir(args.model_path)
        params = load_or_init_params(args.model_path, config, quantize=quantize)
        max_len = min(
            args.context_length or config.max_position_embeddings,
            config.max_position_embeddings,
        )
    # meshed bake (ISSUE 19): --tp/--dp compile the SAME label set over the
    # serving mesh — sharded params, sharded KV, and (with DYN_FUSED_DECODE /
    # DYN_COLLECTIVE_OVERLAP) the shard_map'd fused decode programs. Labels
    # are unchanged: the mesh changes the compiled artifact, not the
    # taxonomy, so the prebake manifest stays closed.
    mesh = kv_sharding = None
    if args.tp > 1 or args.dp > 1:
        from dynamo_tpu.parallel.mesh import build_mesh
        from dynamo_tpu.parallel.sharding import shard_llama

        mesh = build_mesh(tp=args.tp, dp=args.dp)
        params, kv_sharding = shard_llama(mesh, config, params)
    return ModelRunner(
        config,
        params,
        num_blocks=args.num_blocks,
        block_size=args.kv_block_size,
        max_batch=args.max_batch,
        max_model_len=max_len,
        kv_dtype=kv_dtype_from_env(),
        fused_decode=fused_decode_from_env(),
        collective_overlap=collective_overlap_from_env(),
        mesh=mesh,
        kv_sharding=kv_sharding,
    )


def prebake(args) -> dict:
    from dynamo_tpu.runtime.config import setup_jax_compilation_cache

    cache_dir = setup_jax_compilation_cache()
    from dynamo_tpu.ops.sampling import MAX_EOS_IDS

    runner = _build_runner(args)
    bs = runner.block_size
    B = runner.max_batch
    compiled: list[tuple[str, float]] = []

    def bake(label, fn):
        t0 = time.perf_counter()
        fn()
        compiled.append((label, round(time.perf_counter() - t0, 3)))
        print(f"  baked {label}: {compiled[-1][1]}s")

    # one scratch sequence per batch lane, block 0 reserved
    nb_seq = runner.max_blocks_per_seq
    tables = np.zeros((B, nb_seq), np.int32)
    tables[:, 0] = 1

    # prefill: one dispatch per bucket (jit's shape cache keys on bucket)
    for bucket in runner.prefill_buckets:
        ids = list(range(1, bucket // bs + 1))
        bake(
            f"prefill@{bucket}",
            lambda b=bucket, i=ids: runner.prefill([1] * (b - 1), i, 0.0, 1.0, 0),
        )
    # packed + chunked prefill programs
    if runner.prefill_chunk_tokens:
        pack = runner.pack_prefill(
            [(
                [1, 2, 3], [1], 0.0, 1.0, 0, 1.0,
                np.zeros(2, np.uint32), np.full(MAX_EOS_IDS, -1, np.int32), False,
            )]
        )
        bake(
            "prefill_packed",
            lambda: runner.prefill_packed_arrays(**pack),
        )
        bake(
            "prefill_chunk",
            lambda: runner.prefill_chunk(
                [1] * min(runner.prefill_chunk_tokens, bs), 0, bs + 1,
                [1, 2], 0.0, 1.0, 0,
            ),
        )
        # unified mixed prefill+decode steps: one program per chunk-slot
        # count k=1..K, where K mirrors JaxEngine's _mixed_max_slots
        # (ceil(chunk_budget / chunk_tokens); budget defaults to twice
        # the chunk size). Chunk tables are max_blocks_per_seq-wide by
        # construction, so the family is closed — serving never compiles
        # a mixed shape this loop didn't bake.
        budget = args.chunk_budget
        if budget <= 0:
            budget = 2 * runner.prefill_chunk_tokens
        K = max(1, -(-budget // runner.prefill_chunk_tokens))
        chunk = (
            [1] * min(runner.prefill_chunk_tokens, bs), 0, bs + 1,
            [1, 2], 0.0, 1.0, 0, 1.0,
            np.zeros(2, np.uint32),
            np.full(MAX_EOS_IDS, -1, np.int32), False,
        )
        dkeys = np.zeros((B, 2), np.uint32)
        for k in range(1, K + 1):
            bake(
                f"mixed_step@c{k}",
                lambda n=k: runner.mixed_step(
                    [chunk] * n,
                    np.zeros(B, np.int32), np.zeros(B, np.int32), tables,
                    np.zeros(B, np.int32), dkeys,
                    np.zeros(B, np.float32), np.ones(B, np.float32),
                    np.zeros(B, np.int32),
                    eos_ids=np.full((B, MAX_EOS_IDS), -1, np.int32),
                    eos_suppress=np.zeros(B, bool),
                ),
            )
    zeros_i = np.zeros(B, np.int32)
    zeros_f = np.zeros(B, np.float32)
    ones_f = np.ones(B, np.float32)
    # single-step decode (plain + eos-masked variants)
    bake(
        "decode",
        lambda: runner.decode(
            zeros_i, zeros_i, tables, zeros_i, zeros_f, ones_f, zeros_i
        ),
    )
    bake(
        "decode_eos",
        lambda: runner.decode(
            zeros_i, zeros_i, tables, zeros_i, zeros_f, ones_f, zeros_i,
            eos_mask=(
                np.full((B, MAX_EOS_IDS), -1, np.int32), np.zeros(B, bool)
            ),
        ),
    )
    # the unrolled decode horizon (the largest compile of the set)
    H = args.decode_horizon
    if H > 1:
        bake(
            f"decode_multi@H{H}",
            lambda: runner.decode_multi(
                H, zeros_i, zeros_i, tables, zeros_f, ones_f, zeros_i,
                np.zeros((B, 2), np.uint32), np.zeros(B, bool),
                np.ones(B, np.int32), zeros_i,
                np.full((B, MAX_EOS_IDS), -1, np.int32),
            ),
        )
    if args.spec_k > 0:
        bake(
            f"spec_verify@k{args.spec_k}",
            lambda: runner.spec_verify(
                args.spec_k, 0, zeros_i,
                np.full((B, args.spec_k), -1, np.int32), zeros_i, zeros_i,
                tables, zeros_f, ones_f, zeros_i,
                np.zeros((B, 2), np.uint32), np.zeros(B, bool),
                np.ones(B, np.int32), zeros_i,
                np.full((B, MAX_EOS_IDS), -1, np.int32),
            ),
        )
    entries = 0
    if cache_dir and os.path.isdir(cache_dir):
        entries = sum(len(fs) for _, _, fs in os.walk(cache_dir))
    # per-program compile-time table (what the 46.6 s actually buys), then
    # the manifest the engine reads at boot: serve-time recompiles of any
    # label baked here are counted as cause="prebake_miss" — the shipped
    # cache has drifted from the serve shapes
    width = max(len(lbl) for lbl, _ in compiled) if compiled else 8
    print(f"\n  {'program':<{width}}  compile_s")
    for lbl, secs in sorted(compiled, key=lambda p: -p[1]):
        print(f"  {lbl:<{width}}  {secs:9.3f}")
    print(f"  {'TOTAL':<{width}}  {sum(t for _, t in compiled):9.3f}")
    manifest = write_prebake_manifest(cache_dir, compiled)
    if manifest:
        print(f"  manifest: {manifest}")
    return {
        "cache_dir": cache_dir,
        "cache_entries": entries,
        "programs": compiled,
        "manifest": manifest,
        "total_s": round(sum(t for _, t in compiled), 3),
    }


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(
        description="compile the serve-shape program set into the "
        "compilation cache (JAX_COMPILATION_CACHE_DIR, else "
        "<checkout>/.jax_cache) ahead of serving"
    )
    ap.add_argument("--model-path", default=None, help="HF model dir")
    ap.add_argument("--tiny", action="store_true",
                    help="pre-bake the tiny CPU test model instead")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--kv-block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=128)
    ap.add_argument("--context-length", type=int, default=None)
    ap.add_argument("--tp", type=int, default=1,
                    help="bake over a tp-axis mesh (sharded params/KV; "
                    "with DYN_FUSED_DECODE=1 the shard_map'd fused "
                    "decode programs)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel mesh axis for the bake")
    ap.add_argument("--decode-horizon", type=int, default=None)
    ap.add_argument("--spec-k", type=int,
                    default=int(os.environ.get("DYN_SPEC_K", "0") or 0))
    ap.add_argument("--chunk-budget", type=int,
                    default=int(os.environ.get("DYN_CHUNK_BUDGET", "0") or 0),
                    help="per-step mixed prefill token budget (0 = twice "
                    "the chunk size, JaxEngine's default)")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    if not args.tiny and not args.model_path:
        ap.error("--model-path or --tiny required")
    if args.decode_horizon is None:
        from dynamo_tpu.engine.jax_engine.factory import default_decode_horizon

        args.decode_horizon = default_decode_horizon()
    doc = prebake(args)
    print(json.dumps(doc))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
    return doc


if __name__ == "__main__":
    main()
