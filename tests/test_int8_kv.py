"""Int8-resident KV cache parity suite (ISSUE 9).

Covers: exact-scale bit identity vs the bf16 cache, bounded error on
append/rescale writes, multi-token-per-block writes (verify/packed),
pallas in-kernel dequant vs the XLA gather path, greedy parity on the
tiny model, offload->onboard and disagg payload roundtrips with NO
double quantization (mantissa bytes survive verbatim), checksum/
quarantine behavior on int8-resident tier pages, and the HBM-budget
block-count doubling.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.block_manager.layout import LayoutConfig
from dynamo_tpu.block_manager.manager import TieredBlockManager
from dynamo_tpu.disagg.protocols import KvBlockPayload
from dynamo_tpu.engine.jax_engine.engine import JaxEngine, JaxEngineConfig
from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner
from dynamo_tpu.models import llama as L
from dynamo_tpu.ops import kv_quant
from dynamo_tpu.ops.attention import (
    paged_decode_attention,
    paged_verify_attention,
    write_decode_kv,
    write_prefill_kv,
)
from dynamo_tpu.pipeline.context import Context
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

Hkv, NB, BS, D, Hq = 2, 8, 8, 16, 4


def _caches(quantized: bool):
    shape = (Hkv, NB, BS, D)
    if quantized:
        # two layers' containers of one quantized cache: K's and V's
        return kv_quant.make_cache(2, shape, jnp.bfloat16, quantized=True)
    return jnp.zeros(shape, jnp.bfloat16), jnp.zeros(shape, jnp.bfloat16)


def _rand(shape, seed=0, scale=1.0):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape) * scale, jnp.bfloat16
    )


# ------------------------------------------------------------- ops level


def test_exact_scale_roundtrip_is_bit_identical():
    """Integer-valued K/V with per-block absmax 127 quantize losslessly:
    the int8 cache dequantizes to EXACTLY the bf16 cache's contents and
    attention outputs match bit-for-bit."""
    rng = np.random.default_rng(1)
    vals = rng.integers(-127, 128, size=(2 * BS, Hkv, D)).astype(np.float32)
    # force the absmax so every block's scale is exactly 1.0
    vals[0, :, 0] = 127.0
    vals[BS, :, 0] = 127.0
    k_new = jnp.asarray(vals, jnp.bfloat16)
    v_new = jnp.asarray(vals[::-1].copy(), jnp.bfloat16)
    table = jnp.asarray([1, 2], jnp.int32)
    kb, vb = _caches(False)
    kq, vq = _caches(True)
    kb, vb = write_prefill_kv(kb, vb, k_new, v_new, table)
    kq, vq = write_prefill_kv(kq, vq, k_new, v_new, table)
    assert np.array_equal(
        np.asarray(kv_quant.dequantize_layer(kq), np.float32)[:, 1:3],
        np.asarray(kb, np.float32)[:, 1:3],
    )
    q = _rand((2, Hq, D), seed=2)
    bt = jnp.asarray([[1, 2], [1, 2]], jnp.int32)
    cl = jnp.asarray([2 * BS, 2 * BS], jnp.int32)
    ob = paged_decode_attention(q, kb, vb, bt, cl, impl="xla")
    oq = paged_decode_attention(q, kq, vq, bt, cl, impl="xla")
    assert np.array_equal(np.asarray(ob), np.asarray(oq))


def test_append_write_bounded_error_and_scale_growth():
    kq, vq = _caches(True)
    kb, vb = _caches(False)
    # fresh block then appends with growing magnitude (forces rescales)
    for i, mag in enumerate([0.5, 1.0, 4.0, 2.0]):
        tok = _rand((1, Hkv, D), seed=10 + i, scale=mag)
        slot = jnp.asarray([3 * BS + i], jnp.int32)
        kq, vq = write_decode_kv(kq, vq, tok, tok, slot)
        kb, vb = write_decode_kv(kb, vb, tok, tok, slot)
    deq = np.asarray(kv_quant.dequantize_layer(kq), np.float32)[:, 3, :4]
    ref = np.asarray(kb, np.float32)[:, 3, :4]
    amax = np.abs(ref).max()
    assert np.abs(deq - ref).max() <= 2.5 * amax / 127.0


def test_fresh_block_resets_stale_scale():
    """A recycled block's huge old scale must not poison a new sequence's
    small values (write at offset 0 resets)."""
    kq, vq = _caches(True)
    big = _rand((1, Hkv, D), seed=3, scale=1000.0)
    kq, vq = write_decode_kv(kq, vq, big, big, jnp.asarray([5 * BS], jnp.int32))
    assert float(kq["s"][0, 5]) > 1.0
    small = _rand((1, Hkv, D), seed=4, scale=0.01)
    kq, vq = write_decode_kv(
        kq, vq, small, small, jnp.asarray([5 * BS], jnp.int32)
    )
    deq = np.asarray(kv_quant.dequantize_layer(kq), np.float32)[:, 5, 0]
    ref = np.asarray(small, np.float32).transpose(1, 0, 2)[:, 0]
    assert np.abs(deq - ref).max() <= 0.02 * 0.01 + 1e-6


def test_multi_token_same_block_write_matches_sequential():
    """The verify/packed write path (several tokens of one block in one
    call) must land every token — and match the one-token-at-a-time
    semantics within quantization error."""
    toks = _rand((4, Hkv, D), seed=5)
    slots = jnp.asarray([2 * BS, 2 * BS + 1, 2 * BS + 2, 3 * BS], jnp.int32)
    k1, v1 = _caches(True)
    k1, v1 = write_decode_kv(k1, v1, toks, toks, slots)
    k2, v2 = _caches(True)
    for i in range(4):
        k2, v2 = write_decode_kv(
            k2, v2, toks[i : i + 1], toks[i : i + 1], slots[i : i + 1]
        )
    d1 = np.asarray(kv_quant.dequantize_layer(k1), np.float32)[:, 2:4]
    d2 = np.asarray(kv_quant.dequantize_layer(k2), np.float32)[:, 2:4]
    amax = max(np.abs(d2).max(), 1e-6)
    assert np.abs(d1 - d2).max() <= 3.0 * amax / 127.0


@pytest.mark.parametrize("window,softcap", [(None, None), (12, None), (None, 30.0)])
def test_pallas_in_kernel_dequant_matches_xla(window, softcap):
    kq, vq = _caches(True)
    P = 2 * BS
    kq, vq = write_prefill_kv(
        kq, vq, _rand((P, Hkv, D), 6), _rand((P, Hkv, D), 7),
        jnp.asarray([1, 2], jnp.int32),
    )
    q = _rand((2, Hq, D), seed=8)
    bt = jnp.asarray([[1, 2], [1, 2]], jnp.int32)
    cl = jnp.asarray([P - 1, P], jnp.int32)
    a = paged_decode_attention(
        q, kq, vq, bt, cl, impl="xla", window=window, logit_softcap=softcap
    )
    b = paged_decode_attention(
        q, kq, vq, bt, cl, impl="pallas_interpret",
        window=window, logit_softcap=softcap,
    )
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        atol=2e-2, rtol=0,
    )
    S = 2
    qv = _rand((2, S, Hq, D), seed=9)
    pos = jnp.asarray([[P - 2, P - 1], [P - 2, P - 1]], jnp.int32)
    av = paged_verify_attention(
        qv, kq, vq, bt, pos, impl="xla", window=window, logit_softcap=softcap
    )
    bv = paged_verify_attention(
        qv, kq, vq, bt, pos, impl="pallas_interpret",
        window=window, logit_softcap=softcap,
    )
    np.testing.assert_allclose(
        np.asarray(av, np.float32), np.asarray(bv, np.float32),
        atol=2e-2, rtol=0,
    )


# ---------------------------------------------------------- runner level


def _runner(kv_dtype, num_blocks=96, max_batch=2, max_len=96):
    cfg = L.LlamaConfig.tiny(vocab_size=64)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    return ModelRunner(
        cfg, params, num_blocks=num_blocks, block_size=4,
        max_batch=max_batch, max_model_len=max_len, kv_dtype=kv_dtype,
    )


def _greedy_tokens(runner, prompt, steps):
    bs = runner.block_size
    nb = (len(prompt) + steps) // bs + 2
    blocks = list(range(1, nb + 1))
    tables = np.zeros((1, runner.max_blocks_per_seq), np.int32)
    tables[0, :nb] = blocks
    out = runner.fetch_sample(runner.prefill(prompt, blocks, 0.0, 1.0, 0))
    toks = [int(out[0])]
    lps = [float(out[1])]
    pos = len(prompt) - 1
    for _ in range(steps):
        pos += 1
        slot = np.asarray([blocks[pos // bs] * bs + pos % bs], np.int32)
        out = runner.fetch_sample(
            runner.decode(
                np.asarray([toks[-1]], np.int32),
                np.asarray([pos], np.int32),
                tables, slot,
                np.zeros(1, np.float32), np.ones(1, np.float32),
                np.zeros(1, np.int32),
            )
        )
        toks.append(int(out[0]))
        lps.append(float(out[1]))
    return toks, lps


def test_tiny_model_greedy_parity_int8_vs_bf16():
    """Greedy stream + bounded logprob delta on the tiny model: int8-KV
    decode reads quantized history, so logprobs drift within a small
    bound; with this seed the greedy tokens stay identical."""
    prompt = [5, 9, 17, 23, 2, 40, 7, 11]
    tb, lb = _greedy_tokens(_runner("bf16"), prompt, 12)
    tq, lq = _greedy_tokens(_runner("int8"), prompt, 12)
    assert tb[0] == tq[0]  # prefill attends unquantized K/V: same token
    assert np.abs(np.asarray(lb) - np.asarray(lq)).max() < 0.15
    assert tb == tq


def test_extract_blocks_dequantizes_for_legacy_consumers():
    r = _runner("int8")
    blocks = [1, 2, 3]
    r.prefill(list(range(2, 12)), blocks, 0.0, 1.0, 0)
    k, v = r.extract_blocks(blocks)
    assert k.dtype == jnp.bfloat16 and k.shape[2] == 3
    kq, ks, vq, vs = r.extract_blocks_quant(blocks)
    assert kq.dtype == np.int8 and ks.dtype == np.float32
    import ml_dtypes

    np.testing.assert_array_equal(
        np.asarray(k, np.float32),
        (kq.astype(np.float32) * ks[..., None, None]).astype(
            ml_dtypes.bfloat16
        ).astype(np.float32),
    )


def test_disagg_payload_roundtrip_no_recode():
    """extract -> payload -> wire -> land must move the int8 mantissas
    BYTE-IDENTICALLY (the no-double-quantization guarantee)."""
    src = _runner("int8")
    dst = _runner("int8")
    blocks = [1, 2, 3]
    src.prefill(list(range(2, 12)), blocks, 0.0, 1.0, 0)
    kq, ks, vq, vs = src.extract_blocks_quant(blocks)
    payload = KvBlockPayload.from_quantized(kq, ks, vq, vs)
    wire = KvBlockPayload.from_wire(payload.to_wire())
    kq2, ks2, vq2, vs2 = wire.quantized_arrays()
    np.testing.assert_array_equal(kq, kq2)
    np.testing.assert_array_equal(ks, ks2)
    dst.inject_blocks_quant([4, 5, 6], kq2, ks2, vq2, vs2)
    kq3, ks3, vq3, vs3 = dst.extract_blocks_quant([4, 5, 6])
    np.testing.assert_array_equal(kq, kq3)
    np.testing.assert_array_equal(ks, ks3)
    np.testing.assert_array_equal(vq, vq3)
    np.testing.assert_array_equal(vs, vs3)


def test_bf16_payload_lands_on_int8_runner():
    """Raw (bf16) payloads still land on an int8-resident runner — the
    quantize-on-inject path — within quantization error."""
    src = _runner("bf16")
    dst = _runner("int8")
    blocks = [1, 2]
    src.prefill(list(range(2, 10)), blocks, 0.0, 1.0, 0)
    k, v = src.extract_blocks(blocks)
    dst.inject_blocks([7, 8], np.asarray(k), np.asarray(v))
    kd, vd = dst.extract_blocks([7, 8])
    ref = np.asarray(k, np.float32)
    got = np.asarray(kd, np.float32)
    amax = max(np.abs(ref).max(), 1e-6)
    assert np.abs(ref - got).max() <= 2.0 * amax / 127.0


# ------------------------------------------------- tier/engine level


def _layout(cfg, bs=4):
    return LayoutConfig(
        num_layers=cfg.num_layers, page_size=bs,
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        dtype="bfloat16",
    )


def test_tier_roundtrip_verbatim_int8():
    cfg = L.LlamaConfig.tiny(vocab_size=64)
    bm = TieredBlockManager(_layout(cfg), host_blocks=16, wire_codec="int8")
    r = _runner("int8")
    blocks = [1, 2]
    r.prefill(list(range(2, 10)), blocks, 0.0, 1.0, 0)
    kq, ks, vq, vs = r.extract_blocks_quant(blocks)
    assert bm.store_blocks_quant([101, 102], kq, ks, vq, vs) == 2
    kq2, ks2, vq2, vs2 = bm.load_blocks_quant([101, 102])
    np.testing.assert_array_equal(kq, kq2)
    np.testing.assert_array_equal(ks, ks2)
    np.testing.assert_array_equal(vq, vq2)
    np.testing.assert_array_equal(vs, vs2)
    # the dequantizing load agrees with the verbatim one
    kw, _vw = bm.load_blocks([101, 102])
    import ml_dtypes

    np.testing.assert_array_equal(
        kw.view(ml_dtypes.bfloat16).astype(np.float32),
        (kq.astype(np.float32) * ks[..., None, None]).astype(
            ml_dtypes.bfloat16
        ).astype(np.float32),
    )


def test_int8_tier_page_corruption_quarantines():
    from dynamo_tpu import integrity

    if not integrity.enabled():
        pytest.skip("checksums disabled in this environment")
    cfg = L.LlamaConfig.tiny(vocab_size=64)
    bm = TieredBlockManager(_layout(cfg), host_blocks=16, wire_codec="int8")
    r = _runner("int8")
    r.prefill(list(range(2, 10)), [1, 2], 0.0, 1.0, 0)
    kq, ks, vq, vs = r.extract_blocks_quant([1, 2])
    bm.store_blocks_quant([201, 202], kq, ks, vq, vs)
    slot = bm._host[201].index
    bm._k_arena[slot].flat[3] ^= 0x5A  # host-RAM bit flip
    for _ in range(bm.quarantine_after):
        with pytest.raises(integrity.IntegrityError):
            bm.load_blocks_quant([201])
        # re-store so the next verification can fail again
        bm.store_blocks_quant(
            [201], kq[:, :, :1], ks[:, :, :1], vq[:, :, :1], vs[:, :, :1]
        )
        if bm.is_quarantined(201):
            break
        slot = bm._host[201].index
        bm._k_arena[slot].flat[3] ^= 0x5A
    assert bm.is_quarantined(201)
    # quarantined hashes refuse resurrection
    before = bm.stats.quarantine_refused
    assert bm.store_blocks_quant(
        [201], kq[:, :, :1], ks[:, :, :1], vq[:, :, :1], vs[:, :, :1]
    ) == 0
    assert bm.stats.quarantine_refused == before + 1


def _engine(kv_dtype, bm=None):
    cfg = L.LlamaConfig.tiny(vocab_size=64)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    runner = ModelRunner(
        cfg, params, num_blocks=64, block_size=4, max_batch=2,
        max_model_len=64, kv_dtype=kv_dtype,
    )
    return JaxEngine(
        runner,
        JaxEngineConfig(
            max_batch=2, block_size=4, num_blocks=64, max_model_len=64,
            watermark_blocks=2,
        ),
        block_manager=bm,
    )


async def _collect(engine, prompt, n):
    req = PreprocessedRequest(
        token_ids=prompt,
        sampling=SamplingOptions(greedy=True),
        stop=StopConditions(max_tokens=n, ignore_eos=True),
    )
    out = []
    async for o in engine.generate(req, Context()):
        out.extend(o.token_ids)
    return out


async def test_engine_greedy_stream_int8_matches_bf16():
    prompt = list(range(2, 14))
    a = await _collect(_engine("bf16"), prompt, 10)
    b = await _collect(_engine("int8"), prompt, 10)
    assert len(b) == 10
    assert a == b  # tiny-model greedy stays identical under int8 KV


async def test_engine_offload_onboard_roundtrip_int8():
    """Completion offload spills int8 pages verbatim; the prefix hit
    onboards them verbatim; the follow-up stream matches the first."""
    cfg = L.LlamaConfig.tiny(vocab_size=64)
    bm = TieredBlockManager(_layout(cfg), host_blocks=32, wire_codec="int8")
    engine = _engine("int8", bm=bm)
    prompt = list(range(2, 14))
    first = await _collect(engine, prompt, 8)
    for _ in range(100):
        if bm.stats.host_blocks_used:
            break
        await asyncio.sleep(0.02)
    assert bm.stats.host_blocks_used > 0
    hits_before = bm.stats.onboarded
    second = await _collect(engine, prompt, 8)
    assert second == first
    assert bm.stats.onboarded > hits_before  # prefix served from the tier


async def test_prefill_only_ships_int8_payload_verbatim():
    """The prefill-worker role on an int8-resident engine ships the
    device mantissas directly (codec int8, no recode), and the payload
    lands verbatim on another int8 engine."""
    from dynamo_tpu.disagg.protocols import RemotePrefillRequest

    src = _engine("int8")
    req = RemotePrefillRequest(
        request_id="r1", token_ids=list(range(2, 12)), reply_subject="s",
    )
    resp = await src.prefill_only(req)
    assert resp.error is None
    assert resp.payload is not None and resp.payload.codec == "int8"
    dst = _engine("int8")
    n = resp.payload.num_blocks
    ids = list(range(1, n + 1))
    loop = asyncio.get_running_loop()
    await dst._inject_payload(ids, resp.payload, loop)
    kq, ks, vq, vs = dst.runner.extract_blocks_quant(ids)
    kq0, ks0, vq0, vs0 = resp.payload.quantized_arrays()
    np.testing.assert_array_equal(kq0, kq)
    np.testing.assert_array_equal(ks0, ks)
    np.testing.assert_array_equal(vq0, vq)
    np.testing.assert_array_equal(vs0, vs)


def test_default_num_blocks_doubles_for_int8_kv():
    from dynamo_tpu.engine.jax_engine.factory import default_num_blocks

    cfg = L.LlamaConfig.llama3_8b()
    bf16 = default_num_blocks(
        cfg, 8192, 64, quantized=True, kv_dtype="bf16"
    )
    int8 = default_num_blocks(
        cfg, 8192, 64, quantized=True, kv_dtype="int8"
    )
    # both HBM-capped at this shape: int8 must fit ~2x the blocks
    assert int8 >= int(1.8 * bf16)
