"""Engine factory: HF model dir -> (JaxEngine, ModelDeploymentCard).

The `out=jax` path of the CLI (role-equivalent of engine_for() in
launch/dynamo-run/src/lib.rs, pointed at our own engine instead of a
subprocess)."""

from __future__ import annotations

import json
import os
from typing import Optional

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.jax_engine.engine import JaxEngine, JaxEngineConfig
from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner
from dynamo_tpu.engine.jax_engine.weights import load_or_init_params
from dynamo_tpu.model_card import ModelDeploymentCard
from dynamo_tpu.models import (
    cache_kind, config_from_model_dir, forward_for, layer_cache_kinds,
    page_groups, recurrent_layers,
)
from dynamo_tpu.runtime.logging import get_logger

logger = get_logger("dynamo_tpu.engine.factory")


async def build_jax_engine(
    model_path: str,
    name: Optional[str] = None,
    *,
    kv_block_size: int = 16,
    context_length: Optional[int] = None,
    tensor_parallel_size: int = 1,
    # dp here is mesh plumbing (multi-host bring-up spans dp x tp): params
    # and the cache replicate over dp and in-engine compute is identical
    # per dp group. SERVING data parallelism is fleet-level — multiple
    # engine replicas behind the router — same as the reference's dp story;
    # batch-sharded in-engine dp is what __graft_entry__.dryrun_multichip
    # exercises at the SPMD level.
    data_parallel_size: int = 1,
    context_parallel_size: int = 1,
    expert_parallel_size: int = 1,
    max_batch: int = 8,
    num_blocks: Optional[int] = None,
    quantize: Optional[bool] = None,
    rng_seed: int = 0,
    multinode: Optional[object] = None,  # parallel.multihost.MultiNodeConfig
    fabric: Optional[object] = None,  # FabricClient for rendezvous
    lease_id: int = 0,
) -> tuple[object, ModelDeploymentCard]:
    """Build the serving engine. Single-host: returns (JaxEngine, mdc).

    Multi-host (multinode.num_nodes > 1): rendezvous over the fabric
    barrier, `jax.distributed.initialize`, build the mesh over the GLOBAL
    device set, and wrap the runner in the SPMD step channel. The leader
    gets the (JaxEngine, mdc) as usual — its engine loop drives every
    host. Followers get a (FollowerHandle, mdc); call .serve() to replay
    the leader's device calls. Mirrors the reference's MultiNodeConfig +
    etcd barrier bring-up (lib/llm/src/engines.rs:43,
    leader_worker_barrier.rs:137).
    """
    # persistent XLA compile cache before anything traces (idempotent).
    # This is the layer every serving entrypoint funnels through — run.py
    # CLI, sdk service workers spawned by serve.py, operator deployments —
    # so no process pays the cold-compile bill twice for the same program
    # set.
    from dynamo_tpu.runtime.config import setup_jax_compilation_cache

    setup_jax_compilation_cache()
    is_multihost = multinode is not None and multinode.num_nodes > 1
    if is_multihost:
        from dynamo_tpu.parallel.multihost import rendezvous_and_initialize

        await rendezvous_and_initialize(multinode, fabric, lease_id)
    from dynamo_tpu.hub import resolve_model

    model_path = resolve_model(model_path)
    if quantize is None:
        quantize = os.environ.get("DYN_JAX_QUANTIZE_INT8", "0") in ("1", "true")
    kv_dtype = kv_dtype_from_env()
    fused_decode = fused_decode_from_env()
    collective_overlap = collective_overlap_from_env()
    if kv_dtype == "int8" and kv_block_size < 32:
        # Mosaic's int8 sublane tile is (32, 128): a smaller block makes
        # `_pallas_tileable(kv_bits=8)` silently route every serve-time
        # decode through the XLA gather path, quietly forfeiting the
        # int8-KV bandwidth win. Retune instead of degrading.
        logger.warning(
            "DYN_KV_DTYPE=int8 needs kv_block_size >= 32 for the pallas "
            "int8 (32, 128) sublane tile; retuning kv_block_size %d -> 32",
            kv_block_size,
        )
        kv_block_size = 32
    gguf_file = None
    if model_path.endswith(".gguf"):
        # GGUF weights+config (lib/llm/src/gguf/ equivalent); tokenizer
        # must sit next to the file (tokenizer.json in the same dir)
        from dynamo_tpu.gguf import GgufFile, params_from_gguf

        gguf_file = GgufFile(model_path)
        config, params = params_from_gguf(gguf_file)
    else:
        config = config_from_model_dir(model_path)
        refuse_unsupported(
            config, quantize=quantize, kv_dtype=kv_dtype,
            meshed=(
                tensor_parallel_size > 1 or data_parallel_size > 1
                or context_parallel_size > 1 or expert_parallel_size > 1
                or is_multihost
            ),
            fused_decode=fused_decode,
        )
        params = load_or_init_params(
            model_path, config, quantize=quantize, seed=rng_seed
        )
    max_len = min(
        context_length or config.max_position_embeddings,
        config.max_position_embeddings,
    )
    mesh = None
    kv_sharding = None
    window_blocks = None  # the runner's default: as many as `num_blocks`
    if num_blocks is None:
        num_blocks, window_blocks = default_block_pools(
            config, max_len, max_batch,
            block_size=kv_block_size, quantized=quantize,
            tp=tensor_parallel_size, kv_dtype=kv_dtype,
        )
    if (
        tensor_parallel_size > 1
        or data_parallel_size > 1
        or context_parallel_size > 1
        or expert_parallel_size > 1
        or is_multihost
    ):
        from dynamo_tpu.parallel.mesh import build_mesh
        from dynamo_tpu.parallel.sharding import (
            put_global,
            put_local,
            shard_llama,
        )

        mesh = build_mesh(
            tp=tensor_parallel_size,
            dp=data_parallel_size,
            sp=context_parallel_size,
            ep=expert_parallel_size,
        )
        params, kv_sharding = shard_llama(
            mesh, config, params,
            put=put_global if is_multihost else put_local,
        )
    if is_multihost and kv_dtype == "int8":
        # the SPMD step-channel replay path ships bf16 block payloads;
        # int8-resident caches are single-controller for now
        logger.warning(
            "DYN_KV_DTYPE=int8 is not supported multihost; using bf16"
        )
        kv_dtype = "bf16"
    runner = ModelRunner(
        config,
        params,
        num_blocks=num_blocks,
        window_blocks=window_blocks,
        block_size=kv_block_size,
        max_batch=max_batch,
        max_model_len=max_len,
        rng_seed=rng_seed,
        kv_dtype=kv_dtype,
        fused_decode=fused_decode,
        collective_overlap=collective_overlap,
        mesh=mesh,
        kv_sharding=kv_sharding,
        global_arrays=is_multihost,
    )
    if gguf_file is not None:
        try:
            mdc = _gguf_model_card(
                gguf_file, model_path, name,
                kv_block_size=kv_block_size, context_length=max_len,
            )
        finally:
            gguf_file.close()  # the mmap must not leak on error paths
    else:
        mdc = ModelDeploymentCard.from_model_dir(
            model_path,
            name or os.path.basename(os.path.normpath(model_path)),
            kv_block_size=kv_block_size,
            context_length=max_len,
        )
    if is_multihost:
        from dynamo_tpu.parallel.multihost import (
            FollowerHandle,
            SpmdModelRunner,
            SpmdStepChannel,
        )

        channel = SpmdStepChannel(is_leader=multinode.is_leader)
        if not multinode.is_leader:
            # fabric handle => serve_async supervises leader liveness and
            # raises LeaderLostError instead of wedging in a collective
            return FollowerHandle(runner, channel, fabric=fabric), mdc
        runner = SpmdModelRunner(runner, channel)
    engine = JaxEngine(
        runner,
        JaxEngineConfig(
            max_batch=max_batch,
            block_size=kv_block_size,
            num_blocks=num_blocks,
            max_model_len=max_len,
            rng_seed=rng_seed,
            decode_horizon=default_decode_horizon(),
            **spec_decode_settings(),
        ),
        block_manager=_maybe_block_manager(config, kv_block_size),
    )
    logger.info("jax engine built: %s", json.dumps(_built_facts(engine)))
    return engine, mdc


def refuse_unsupported(
    config, *, quantize: bool = False, kv_dtype: str = "bf16",
    meshed: bool = False, fused_decode: bool = False,
) -> None:
    """Say at start-up, in words, what a model is not served with yet;
    nothing falls back in silence. A grouped-query model passes untouched.

    A model whose layers keep a latent plane (`models/mla_moe.py`) is served
    in bfloat16 on one chip: no int8 weights, no int8-resident cache, no
    mesh, no fused decode step, no block-manager tiers, no speculative
    decoding. A model with a recurrent layer (`models/hybrid_ssm.py`: a
    state slot a sequence beside paged keys and values; `models/conv_moe.py`:
    a short convolution's tail a sequence, and routed experts;
    `models/ssm2_moe.py`: a Mamba-2 state and tail a sequence, expert layers
    that keep nothing and may hold a share of their experts) is served the
    same way and refuses the same six and, besides, disaggregated transfer,
    peer pulls and live handoff (`ModelRunner.require_block_transfer`);
    prefix reuse it simply does not offer (the engine publishes no block
    hashes for it). A model whose paged layers are of two groups
    (`models/afmoe.py`: window layers that give their pages back beside
    layers that keep every position) refuses the same, for its own reasons."""
    n = recurrent_layers(config)
    kind = cache_kind(config)
    if n:
        what = (
            f"keeps a recurrent state a sequence in {n} of its "
            f"{config.num_layers} layers"
        )
        why = {
            "int8_weights": "not implemented for state-space mixers, short "
            "convolutions and expert stacks",
            "int8_cache": "the slots keep their own dtype and the pages "
            "beside them are served in bfloat16",
            "mesh": "the slots' channels have no sharding rule yet, and the "
            "exchange between the chips that share an expert layer is not "
            "written (a held share of the experts runs on one chip without "
            "it)",
            "fused_decode": "its kernels are the grouped-query block's",
            "tiers": "and with them prefix reuse: a block of keys and values "
            "without the state at its boundary cannot resume a sequence, and "
            "no state snapshots are kept yet",
            "speculation": "a rejected draft would need the state rolled back",
        }
    elif len(page_groups(config)) > 1:
        window = page_groups(config)[1].window
        what = (
            f"keeps the last {window} positions alone in its window layers, "
            "which give their blocks back, beside layers that keep every "
            "position"
        )
        why = {
            "int8_weights": "not implemented for expert stacks and the "
            "gated attention",
            "int8_cache": "a block's scale regrows on append, which the "
            "paged call that appends does not do, and the two pools have no "
            "scale planes",
            "mesh": "the two pools have no sharding rule yet, and the "
            "exchange between the chips that share an expert layer is not "
            "written (a held share of the experts runs on one chip without "
            "it)",
            "fused_decode": "its kernels are the grouped-query block's",
            "tiers": "and with them prefix reuse: a tier's block is every "
            "layer's rows under one id, and a window layer's are gone once "
            "the window has left them",
            "speculation": "no verify program over two page groups",
        }
    elif kind.name != "kv_heads":
        what = f"keeps a {kind.name} cache of {kind.width} values a token"
        why = {
            "int8_weights": "not implemented for expert stacks",
            "int8_cache": "its scales are kept by head, and a latent plane "
            "has none",
            "mesh": "the latent plane has no head axis to shard and the "
            "experts' share has no add-up test yet",
            "fused_decode": "its kernels are the grouped-query block's",
            "tiers": "a tier's layout is keys and values by head",
            "speculation": "no verify program for latent attention",
        }
    else:
        return
    asked = {
        "int8_weights": quantize,
        "int8_cache": kv_dtype == "int8",
        "mesh": meshed,
        "fused_decode": fused_decode,
        "tiers": float(os.environ.get("DYN_KV_HOST_OFFLOAD_GB", "0") or 0) > 0,
        "speculation": spec_decode_settings()["spec_k"] > 0,
    }
    refused = [
        f"{_OPTION_WORDS[option]}: {why[option]}"
        for option, on in asked.items() if on
    ]
    if refused:
        raise ValueError(
            f"{type(config).__name__} {what} and is served in bfloat16 on "
            "one chip; not implemented for it: " + "; ".join(refused)
        )


# an option `refuse_unsupported` may refuse, in the words its refusal uses
_OPTION_WORDS = {
    "int8_weights": "int8 weights (DYN_JAX_QUANTIZE_INT8)",
    "int8_cache": "an int8-resident cache (DYN_KV_DTYPE=int8)",
    "mesh": "a tensor-, expert-, data- or context-parallel mesh or several "
    "hosts",
    "fused_decode": "the fused decode step (DYN_FUSED_DECODE)",
    "tiers": "block-manager tiers (DYN_KV_HOST_OFFLOAD_GB)",
    "speculation": "speculative decoding (DYN_SPEC_K)",
}


def _built_facts(engine: JaxEngine) -> dict:
    """What this process holds, for the one log line a launcher or smoke
    reads back: only the process that owns the chip can name it."""
    from dynamo_tpu.native import native_available
    from dynamo_tpu.runtime.config import jax_cache_dir

    # memory_stats() exists only for this process's own devices: on a
    # multihost leader the other ranks' devices are in jax.devices() too
    devices = jax.local_devices()
    runner = engine.runner
    stats = [d.memory_stats() or {} for d in devices]
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": jax.device_count(),
        "attn_impl": runner.attn_impl,
        "decode_horizon": engine.config.decode_horizon,
        "mixed_step": engine.config.mixed_step,
        "kv_quantized": runner.kv_quantized,
        "num_blocks": runner.num_blocks,
        "window_blocks": runner.window_blocks,
        "max_batch": runner.max_batch,
        "max_model_len": runner.max_model_len,
        "mesh": dict(runner.mesh.shape) if runner.mesh is not None else None,
        "cache_dir": jax_cache_dir(),
        "native_blockhash": native_available(),
        "bytes_in_use": [s.get("bytes_in_use") for s in stats],
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
        "bytes_limit": [s.get("bytes_limit") for s in stats],
    }


def _maybe_block_manager(config, kv_block_size: int):
    """Tiered KV offload (the KVBM role, reference block_manager/):
    DYN_KV_HOST_OFFLOAD_GB > 0 enables the host tier (G2), sized in
    whole blocks; DYN_KV_DISK_DIR adds the disk tier (G3), capped at
    DYN_KV_DISK_GB (0 = unbounded). Unset => disabled, matching the
    reference where KVBM is opt-in per deployment."""
    gb = float(os.environ.get("DYN_KV_HOST_OFFLOAD_GB", "0") or 0)
    if gb <= 0:
        return None
    from dynamo_tpu.block_manager import LayoutConfig, TieredBlockManager

    layout = LayoutConfig(
        num_layers=config.num_layers,
        page_size=kv_block_size,
        num_kv_heads=config.num_kv_heads,
        head_dim=config.head_dim,
        dtype="bfloat16",
    )
    from dynamo_tpu.disagg.protocols import wire_codec_from_env

    # DYN_KV_WIRE=int8 halves tier bytes (per-block-scale quantized
    # storage), so the same GB budget holds twice the blocks. An
    # int8-RESIDENT device cache (DYN_KV_DTYPE=int8) forces int8 tiers:
    # device pages then spill/onboard VERBATIM (mantissas+scales, no
    # recode, no double quantization).
    codec = wire_codec_from_env()
    if kv_dtype_from_env() == "int8":
        codec = "int8"
    block_nbytes = layout.block_nbytes
    if codec == "int8":
        block_nbytes = block_nbytes // layout.itemsize  # int8 mantissas
    host_blocks = max(1, int(gb * 2**30 // block_nbytes))
    disk_dir = os.environ.get("DYN_KV_DISK_DIR") or None
    disk_blocks = 0
    if disk_dir:
        disk_gb = float(os.environ.get("DYN_KV_DISK_GB", "0") or 0)
        disk_blocks = int(disk_gb * 2**30 // block_nbytes)
    logger.info(
        "KV offload tiers: host %d blocks (%.2f GiB, codec %s)%s",
        host_blocks, gb, codec,
        f", disk at {disk_dir} ({disk_blocks or 'unbounded'} blocks)"
        if disk_dir else "",
    )
    manager = TieredBlockManager(
        layout, host_blocks=host_blocks,
        disk_dir=disk_dir, disk_blocks=disk_blocks,
        wire_codec=codec,
    )
    warm_dir = os.environ.get("DYN_WARM_RESTART_DIR")
    if warm_dir:
        # warm restart: restore checksummed KVB2 checkpoint pages (written
        # at the previous incarnation's SIGTERM drain) into the tiers —
        # the worker boots with a hot prefix cache; corrupt pages are
        # refused and simply recompute. run_endpoint republishes the
        # restored block adverts once the KV event publisher is wired.
        manager.restore(warm_dir)
    return manager


def kv_dtype_from_env() -> str:
    """DYN_KV_DTYPE=int8|bf16 (default bf16): device-resident KV cache
    dtype. int8 stores the paged cache as mantissas + per-(layer, head,
    block) scales (ops/kv_quant.py) — ~2x the blocks per GB and ~half the
    per-step decode KV HBM traffic, with dequant inside the attention
    kernels. bf16 (the default) is bit-exact and unchanged."""
    v = os.environ.get("DYN_KV_DTYPE", "bf16").strip().lower()
    return "int8" if v == "int8" else "bf16"


def fused_decode_from_env() -> bool:
    """DYN_FUSED_DECODE=1: fuse the decode step's norm+QKV+rope and
    attn-out+O-proj+residual into one pallas program each (ops/linear.py;
    shard_map'd over tp under a mesh — ops/collective.py). Off by default
    until parity is proven per deployment."""
    return os.environ.get("DYN_FUSED_DECODE", "0") in ("1", "true", "yes")


def collective_overlap_from_env() -> bool:
    """DYN_COLLECTIVE_OVERLAP=1: decompose the meshed fused decode step's
    tp all-reduces into reduce-scatter/all-gather rings pipelined against
    the o-proj/MLP matmul chunks (ops/collective.fused_tail_overlap).
    Token-identical to the plain psum path, not bit-identical (ring
    summation order); inert without fused decode + a tp>1 mesh."""
    return os.environ.get("DYN_COLLECTIVE_OVERLAP", "0") in (
        "1", "true", "yes",
    )


def spec_decode_settings() -> dict:
    """Self-drafting speculative decoding knobs (JaxEngineConfig fields):

      DYN_SPEC_K           draft tokens per lane per dispatch (0 = off,
                           the default — spec decoding is opt-in)
      DYN_SPEC_DRAFTER     "ngram" (prompt-lookup; the only kind today)
      DYN_SPEC_NGRAM_MIN / DYN_SPEC_NGRAM_MAX   lookup n-gram bounds
    """
    return {
        "spec_k": max(0, int(os.environ.get("DYN_SPEC_K", "0") or 0)),
        "spec_drafter": os.environ.get("DYN_SPEC_DRAFTER", "ngram"),
        "spec_ngram_min": max(
            1, int(os.environ.get("DYN_SPEC_NGRAM_MIN", "2") or 2)
        ),
        "spec_ngram_max": max(
            1, int(os.environ.get("DYN_SPEC_NGRAM_MAX", "4") or 4)
        ),
        "spec_min_coverage": float(
            os.environ.get("DYN_SPEC_COVERAGE", "0.5") or 0.5
        ),
    }


def default_decode_horizon() -> int:
    """Horizon decode default: DYN_DECODE_HORIZON env override, else 4 on
    TPU, 1 elsewhere (CPU tests exercise the single-step path unless they
    opt in). The default of 4 has not been measured against H=1 on the
    chip machine (ROADMAP S6); the unrolled horizon's compile time is
    linear in H."""
    override = os.environ.get("DYN_DECODE_HORIZON")
    if override:
        return max(1, int(override))
    return 4 if jax.default_backend() == "tpu" else 1


def _gguf_model_card(
    gguf_file, model_path: str, name: Optional[str],
    *, kv_block_size: int, context_length: int,
) -> ModelDeploymentCard:
    """Model card for a .gguf deployment: sidecar tokenizer files next to
    the file win; otherwise the tokenizer embedded in the GGUF metadata
    serves (tokenizer.ggml.* -> native SentencePiece; reference
    gguf_tokenizer.rs). The embedded chat template rides along too."""
    model_dir = os.path.dirname(os.path.abspath(model_path))
    card_name = name or os.path.basename(model_path).removesuffix(".gguf")
    try:
        return ModelDeploymentCard.from_model_dir(
            model_dir, card_name,
            kv_block_size=kv_block_size, context_length=context_length,
        )
    except FileNotFoundError:
        pass
    from dynamo_tpu.gguf import tokenizer_from_gguf

    tok = tokenizer_from_gguf(gguf_file)
    if tok is None:
        raise FileNotFoundError(
            f"{model_path}: no tokenizer.json/tokenizer.model beside the "
            "file and no tokenizer.ggml metadata inside it"
        )
    # bos/eos STRINGS feed chat templates ('{{ bos_token }}' is standard
    # in published GGUF templates — empty strings would silently drop them)
    md = gguf_file.metadata
    tokens = md.get("tokenizer.ggml.tokens") or []

    def tok_str(key: str) -> str:
        tid = md.get(key)
        if isinstance(tid, int) and 0 <= tid < len(tokens):
            return tokens[tid]
        return ""

    return ModelDeploymentCard.from_tokenizer(
        card_name, tok,
        chat_template=md.get("tokenizer.chat_template"),
        bos_token=tok_str("tokenizer.ggml.bos_token_id"),
        eos_token=tok_str("tokenizer.ggml.eos_token_id"),
        kv_block_size=kv_block_size,
        context_length=context_length,
    )


def hbm_budget_bytes() -> int:
    """Per-device memory budget: the DYN_HBM_GB override, else what the
    device reports. A TPU that reports nothing is an error; only a backend
    without memory stats (the CPU of tests) gets the 16 GiB assumption."""
    override = os.environ.get("DYN_HBM_GB")
    if override:
        return int(float(override) * 2**30)
    device = jax.local_devices()[0]
    stats = device.memory_stats()
    if stats and "bytes_limit" in stats:
        return int(stats["bytes_limit"])
    if device.platform == "tpu":
        raise RuntimeError(
            f"{device} reports no memory_stats()['bytes_limit']; set "
            "DYN_HBM_GB to size the KV cache"
        )
    return 16 * 2**30


def default_num_blocks(config, max_len: int, max_batch: int, **kw) -> int:
    """`default_block_pools` of a model whose paged layers are one group
    (or the full group's share, of one with two)."""
    return default_block_pools(config, max_len, max_batch, **kw)[0]


def default_block_pools(
    config,
    max_len: int,
    max_batch: int,
    *,
    block_size: int = 16,
    quantized: bool = False,
    tp: int = 1,
    utilization: float = 0.85,
    kv_dtype: str = "bf16",
) -> tuple[int, int]:
    """(blocks, blocks of the window group's pool: 0 where there is none).
    Blocks for every batch lane at full context plus slack, capped so
    weights + KV fit the per-device HBM budget. What a block costs comes
    from the cache the config's layers declare (`models.cache_kind`), what
    the weights cost from its family's `param_count`.

    Two page groups (`models.page_groups`): a block of a group costs its own
    layers' rows; a lane needs its whole context of the full group and no
    more than the window, a chunk and a block or two of the other. Where the
    budget is short of every lane at full context, both pools shrink by one
    factor from what a batch needs whose every lane stands at three quarters
    of the served context: between a lane's mean context over a life that
    runs to the end (a half, which would starve the full group of a batch of
    long sessions) and the end itself (which would starve the window group
    of a batch of short ones, whose rows cost it four times the bytes)."""
    per_seq = (max_len + block_size - 1) // block_size
    want = max_batch * per_seq + 64
    model = forward_for(config)
    kind = cache_kind(config)
    # int8 quantization applies to dense projections only; MoE expert
    # stacks stay bf16 (see init_params / load_hf_safetensors), so count
    # them at 2 bytes regardless. Experts also divide over ep, not tp,
    # but tp is the conservative divisor available here.
    expert_params = model.expert_param_count(config)
    dense_params = model.param_count(config) - expert_params
    weight_bytes = (
        dense_params * (1 if quantized else 2) + expert_params * 2
    ) // tp
    # int8-resident KV: 1 byte/value + one f32 scale per (layer, head,
    # block) — the same HBM budget holds ~2x the blocks
    kv_itemsize = 1 if kv_dtype == "int8" else 2
    heads = max(1, kind.heads // tp)
    scale_bytes = (
        4 * config.num_layers * heads * kind.planes
        if kv_dtype == "int8"
        else 0
    )
    # a block holds rows of the layers that keep rows (all of them, but in
    # a model with recurrent layers); those layers' slots, one a lane and
    # the null lane's, come off the budget first
    kinds = layer_cache_kinds(config)
    groups = page_groups(config)
    slot_bytes = (max_batch + 1) * sum(k.slot_bytes for k in kinds)
    budget = int(hbm_budget_bytes() * utilization) - weight_bytes - slot_bytes

    def block_bytes(group) -> int:
        layers = sum(k == group for k in kinds)
        return (
            layers * block_size
            * group.stored_values_per_token(tp) * kv_itemsize
            + scale_bytes * layers // config.num_layers
        )

    if len(groups) == 1:
        cap = max(16, budget // max(1, block_bytes(groups[0])))
        if want > cap:
            logger.warning(
                "KV cache capped by HBM budget: want %d blocks, fit %d", want, cap
            )
        return min(want, cap), 0
    full, window = groups
    # a lane's most of the window group: the window, a chunk that attends
    # across its edge, and two blocks of slack for what a launch ahead holds
    lane_window = min(per_seq, -(-(window.window + 512) // block_size) + 2)
    want_window = max_batch * lane_window + 64
    if want * block_bytes(full) + want_window * block_bytes(window) <= budget:
        return want, want_window
    ref_full = max_batch * -(-3 * per_seq // 4) + 64
    shrink = budget / (
        ref_full * block_bytes(full) + want_window * block_bytes(window)
    )
    pools = (
        min(want, max(16, int(ref_full * shrink))),
        min(want_window, max(16, int(want_window * shrink))),
    )
    logger.warning(
        "KV cache capped by HBM budget: want %d + %d blocks, fit %d + %d",
        want, want_window, *pools,
    )
    return pools
