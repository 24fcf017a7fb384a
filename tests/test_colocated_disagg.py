"""Device-native (colocated) disagg KV transfer: same-process P/D engines
exchanging KV blocks as device arrays via jax.device_put — the TPU-native
stand-in for the reference's GPUDirect-RDMA NIXL plane
(docs/architecture/disagg_serving.md:76-118). The msgpack/TCP wire path is
the cross-process fallback; these tests assert the device path is
byte-equivalent to local serving and never touches the wire codec."""

import asyncio
import time

import jax
import numpy as np
import pytest

# engine-pair parity suite (~2 min of compiles): slow tier; the default
# tier still covers the colocated role through test_disagg's wire-path
# short-prompt + queue tests
pytestmark = pytest.mark.slow

from dynamo_tpu.disagg.colocated import ColocatedPrefillClient
from dynamo_tpu.disagg.router import DisaggConfig, DisaggregatedRouter
from dynamo_tpu.engine.jax_engine.engine import JaxEngine, JaxEngineConfig
from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner
from dynamo_tpu.fabric.client import FabricClient
from dynamo_tpu.models import llama as L
from dynamo_tpu.parallel.mesh import build_mesh
from dynamo_tpu.parallel.sharding import shard_llama
from dynamo_tpu.pipeline.context import Context
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

BLOCK = 4


def make_engine(mesh=None, devices=None, tp=1, kv_heads=None, **kw):
    import dataclasses

    cfg = L.LlamaConfig.tiny(vocab_size=64)
    if kv_heads:  # tp=4 needs >= 4 kv heads to shard
        cfg = dataclasses.replace(cfg, num_kv_heads=kv_heads)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    kv_sharding = None
    if devices is not None:
        mesh = build_mesh(tp=tp, devices=devices)
    if mesh is not None:
        params, kv_sharding = shard_llama(mesh, cfg, params)
    runner = ModelRunner(
        cfg, params, num_blocks=64, block_size=BLOCK, max_batch=4,
        max_model_len=64, mesh=mesh, kv_sharding=kv_sharding, **kw,
    )
    return JaxEngine(
        runner,
        JaxEngineConfig(
            max_batch=4, block_size=BLOCK, num_blocks=64, max_model_len=64
        ),
    )


async def collect_tokens(engine, prompt, max_tokens=8):
    req = PreprocessedRequest(
        token_ids=prompt,
        sampling=SamplingOptions(greedy=True),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
    )
    toks = []
    async for out in engine.generate(req, Context()):
        toks.extend(out.token_ids)
    return toks


def wire_decode_engine(prefill_engine):
    """Decode engine whose long prompts go to the colocated prefill engine
    over the DEVICE path."""
    router = DisaggregatedRouter(
        FabricClient.in_process(), "colo",
        DisaggConfig(max_local_prefill_length=4, max_prefill_queue_size=100),
    )
    router._queue_depth_cache = 0
    client = ColocatedPrefillClient(prefill_engine, block_size=BLOCK)
    return make_engine(), router, client


async def test_colocated_device_path_matches_local():
    prefill_engine = make_engine()
    decode_engine, router, client = wire_decode_engine(prefill_engine)
    decode_engine.disagg_router = router
    decode_engine.remote_prefill_client = client

    prompts = [list(range(2, 2 + n)) for n in (9, 17, 23)]
    refs = [await collect_tokens(make_engine(), p) for p in prompts]
    outs = [await collect_tokens(decode_engine, p) for p in prompts]
    assert outs == refs
    await decode_engine.close()
    await prefill_engine.close()


async def test_colocated_mesh_to_mesh_distinct_devices():
    """Prefill on devices[0:2] (tp=2), decode on devices[2:4] (tp=2): the
    KV blocks cross meshes via device_put with resharding — the actual
    ICI-copy topology of a colocated P/D slice."""
    devs = jax.devices()
    assert len(devs) >= 4
    prefill_engine = make_engine(devices=devs[0:2], tp=2)
    decode_engine = make_engine(devices=devs[2:4], tp=2)
    router = DisaggregatedRouter(
        FabricClient.in_process(), "colo2",
        DisaggConfig(max_local_prefill_length=4, max_prefill_queue_size=100),
    )
    router._queue_depth_cache = 0
    decode_engine.disagg_router = router
    decode_engine.remote_prefill_client = ColocatedPrefillClient(
        prefill_engine, block_size=BLOCK
    )
    prompt = list(range(2, 19))
    ref = await collect_tokens(make_engine(), prompt)
    got = await collect_tokens(decode_engine, prompt)
    assert got == ref
    # every cache array stayed on its own mesh
    assert {d for d in decode_engine.runner.k_cache[0].devices()} == set(devs[2:4])
    assert {d for d in prefill_engine.runner.k_cache[0].devices()} == set(devs[0:2])
    await decode_engine.close()
    await prefill_engine.close()


async def _assert_asymmetric_matches_local(
    p_devs, p_tp, d_devs, d_tp, ns, kv_heads=None
):
    """P(tp=p_tp) -> D(tp=d_tp) on DISTINCT device sets: KV blocks cross
    meshes with a real reshard (different head partitioning), the case
    block_copy.cu exists for in the reference (its canonical benchmark
    shape is 4x P(TP1) + 1x D(TP4), examples/llm/benchmarks/README.md:77).
    device_put under the destination sharding must produce bit-identical
    decode vs serving locally."""
    prefill_engine = make_engine(devices=p_devs, tp=p_tp, kv_heads=kv_heads)
    decode_engine = make_engine(devices=d_devs, tp=d_tp, kv_heads=kv_heads)
    router = DisaggregatedRouter(
        FabricClient.in_process(), ns,
        DisaggConfig(max_local_prefill_length=4, max_prefill_queue_size=100),
    )
    router._queue_depth_cache = 0
    decode_engine.disagg_router = router
    decode_engine.remote_prefill_client = ColocatedPrefillClient(
        prefill_engine, block_size=BLOCK
    )
    prompts = [list(range(2, 2 + n)) for n in (9, 17)]
    refs = [
        await collect_tokens(make_engine(kv_heads=kv_heads), p)
        for p in prompts
    ]
    outs = [await collect_tokens(decode_engine, p) for p in prompts]
    assert outs == refs
    assert {d for d in decode_engine.runner.k_cache[0].devices()} == set(d_devs)
    assert {d for d in prefill_engine.runner.k_cache[0].devices()} == set(p_devs)
    await decode_engine.close()
    await prefill_engine.close()


async def test_colocated_asymmetric_tp1_to_tp2():
    devs = jax.devices()
    assert len(devs) >= 3
    await _assert_asymmetric_matches_local(
        devs[0:1], 1, devs[1:3], 2, "asym12"
    )


async def test_colocated_asymmetric_tp2_to_tp4():
    devs = jax.devices()
    assert len(devs) >= 8
    await _assert_asymmetric_matches_local(
        devs[0:2], 2, devs[4:8], 4, "asym24", kv_heads=4
    )


async def test_device_path_skips_wire_codec(monkeypatch):
    """The device path must never serialize: poison the wire codec and the
    colocated transfer still completes."""
    import dynamo_tpu.disagg.transfer as transfer

    def boom(*a, **kw):  # noqa: ARG001
        raise AssertionError("wire codec used on the device path")

    monkeypatch.setattr(transfer, "to_wire_array", boom)
    monkeypatch.setattr(transfer, "from_wire_array", boom)

    prefill_engine = make_engine()
    decode_engine, router, client = wire_decode_engine(prefill_engine)
    decode_engine.disagg_router = router
    decode_engine.remote_prefill_client = client
    prompt = list(range(2, 15))
    ref = await collect_tokens(make_engine(), prompt)
    got = await collect_tokens(decode_engine, prompt)
    assert got == ref
    await decode_engine.close()
    await prefill_engine.close()
