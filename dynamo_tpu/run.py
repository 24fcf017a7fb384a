"""`python -m dynamo_tpu.run` — the dynamo-run equivalent CLI.

Role-equivalent of launch/dynamo-run (src/main.rs:39, opt.rs):

    python -m dynamo_tpu.run in=http out=echo_full --model-name test \\
        --model-path /path/to/hf/dir --http-port 8080

in  = http | text | batch:FILE.jsonl | dyn://ns.comp.ep
out = echo_core | echo_full | jax | dyn   (dyn = route to discovered workers)
"""

from __future__ import annotations

import argparse
import asyncio
import sys
from typing import Optional

from dynamo_tpu.engine.echo import EchoEngineCore, EchoEngineFull
from dynamo_tpu.entrypoint.inputs import EngineConfig, run_batch, run_input, run_text
from dynamo_tpu.model_card import ModelDeploymentCard
from dynamo_tpu.pipeline.router import RouterMode
from dynamo_tpu.runtime import logging as dlog
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.tokenizer import TokenizerWrapper


def build_test_mdc(name: str) -> ModelDeploymentCard:
    """A self-contained word-level model card for echo engines (no files)."""
    from tokenizers import Tokenizer, models, pre_tokenizers

    vocab = {"<unk>": 0, "<s>": 1, "</s>": 2}
    tok = Tokenizer(models.WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    wrapper = TokenizerWrapper(tok, eos_token_ids=[2])
    return ModelDeploymentCard.from_tokenizer(name, wrapper)


def parse_args(argv: Optional[list[str]] = None) -> argparse.Namespace:
    parser = argparse.ArgumentParser(prog="dynamo_tpu.run", description=__doc__)
    parser.add_argument("inout", nargs="*", help="in=... out=...")
    parser.add_argument("--model-path", default=None)
    parser.add_argument("--model-name", default=None)
    parser.add_argument("--http-port", type=int, default=8080)
    parser.add_argument("--http-host", default="0.0.0.0")
    parser.add_argument("--kv-block-size", type=int, default=16)
    parser.add_argument("--context-length", type=int, default=None)
    parser.add_argument(
        "--router-mode",
        choices=[m.value for m in RouterMode],
        default="round_robin",
    )
    parser.add_argument("--endpoint", default="dynamo.backend.generate")
    parser.add_argument(
        "--tensor-parallel-size", type=int, default=1,
        help="TP degree for out=jax engines",
    )
    parser.add_argument(
        "--num-blocks", type=int, default=None,
        help="KV cache blocks (default: sized to the HBM budget)",
    )
    parser.add_argument("--max-batch", type=int, default=8)
    parser.add_argument(
        "--kv-overlap-score-weight", type=float, default=1.0,
        help="KV router: weight on prefill (non-cached) blocks in the cost",
    )
    parser.add_argument(
        "--router-temperature", type=float, default=0.5,
        help="KV router: softmax sampling temperature (0 = argmin)",
    )
    parser.add_argument(
        "--no-kv-events", action="store_true",
        help="KV router: use TTL-based ApproxKvIndexer instead of events",
    )
    parser.add_argument(
        "--request-template", default=None,
        help="JSON file with default model/temperature/max_completion_tokens "
        "applied to requests that omit them (ref request_template.rs)",
    )
    args = parser.parse_args(argv)
    args.in_opt = "http"
    args.out_opt = "echo_full"
    for tok in args.inout:
        if tok.startswith("in="):
            args.in_opt = tok[3:]
        elif tok.startswith("out="):
            args.out_opt = tok[4:]
        elif args.model_path is None:
            args.model_path = tok
    return args


async def amain(args: argparse.Namespace) -> None:
    # force: this IS the process entrypoint — honor the child's DYN_LOG /
    # DYN_LOGGING_JSONL even when an early import already initialized
    # logging (serve.py children tighten per-service log levels this way)
    dlog.init(force=True)
    name = args.model_name or (args.model_path or "echo-model")
    jax_engine = None
    if args.out_opt == "jax":
        # Built BEFORE the runtime exists: importing jax, reaching the
        # chip and making 7B of weights stall this process for longer than
        # the lease TTL, and a runtime that cannot send keepalives loses
        # its primary lease and self-fences before the first request.
        from dynamo_tpu.engine.jax_engine.factory import build_jax_engine

        if not args.model_path:
            raise SystemExit("out=jax requires a --model-path (HF dir)")
        jax_engine = await build_jax_engine(
            args.model_path,
            name,
            kv_block_size=args.kv_block_size,
            context_length=args.context_length,
            tensor_parallel_size=args.tensor_parallel_size,
            num_blocks=args.num_blocks,
            max_batch=args.max_batch,
        )
    drt = await DistributedRuntime.from_settings()
    try:
        if args.out_opt == "dyn":
            from dynamo_tpu.kv_router.scheduler import KvRouterConfig

            config = EngineConfig.dynamic(
                RouterMode(args.router_mode),
                kv_router_config=KvRouterConfig(
                    overlap_score_weight=args.kv_overlap_score_weight,
                    router_temperature=args.router_temperature,
                    use_kv_events=not args.no_kv_events,
                ),
            )
        elif args.out_opt in ("echo_core", "echo_full"):
            if args.model_path:
                mdc = ModelDeploymentCard.from_model_dir(
                    args.model_path,
                    name,
                    kv_block_size=args.kv_block_size,
                    context_length=args.context_length,
                )
            else:
                mdc = build_test_mdc(name)
            engine = EchoEngineCore() if args.out_opt == "echo_core" else EchoEngineFull()
            config = EngineConfig.static_(engine, mdc)
        elif args.out_opt == "mocker":
            from dynamo_tpu.engine.mocker import MockEngine, MockEngineArgs

            mdc = (
                ModelDeploymentCard.from_model_dir(
                    args.model_path,
                    name,
                    kv_block_size=args.kv_block_size,
                    context_length=args.context_length,
                )
                if args.model_path
                else build_test_mdc(name)
            )
            engine = MockEngine(
                MockEngineArgs(
                    num_blocks=args.num_blocks or 1024,
                    block_size=args.kv_block_size,
                    max_batch=args.max_batch,
                )
            )
            config = EngineConfig.static_(engine, mdc)
        elif jax_engine is not None:
            config = EngineConfig.static_(*jax_engine)
        else:
            raise SystemExit(f"unknown out={args.out_opt}")
        if args.request_template:
            from dynamo_tpu.request_template import RequestTemplate

            config.request_template = RequestTemplate.load(args.request_template)
        if args.in_opt == "http":
            from dynamo_tpu.entrypoint.inputs import serve_http_forever

            await serve_http_forever(drt, config, args.http_host, args.http_port)
        else:
            await run_input(drt, args.in_opt, config, args.http_port, args.http_host)
    finally:
        await drt.close()


def main() -> None:
    args = parse_args()
    try:
        asyncio.run(amain(args))
    except KeyboardInterrupt:
        print("\ninterrupted", file=sys.stderr)


if __name__ == "__main__":
    main()
