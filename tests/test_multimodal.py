"""Multimodal E/P/D: vision encoder, prompt splice, encode disaggregation.

(reference examples/multimodal/components/{encode_worker,prefill_worker}.py
+ connect/__init__.py embedding transfer — VERDICT r3 missing #2)"""

import base64
import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.util import layer_caches
from dynamo_tpu.models import llama as L
from dynamo_tpu.multimodal.processor import (
    expand_image_prompt,
    load_image_array,
    preprocess_pixels,
)
from dynamo_tpu.multimodal.vision import (
    ViTConfig,
    encode_pixels,
    init_vit_params,
)

VIT = ViTConfig(image_size=32, patch_size=8, hidden_size=32, num_layers=1,
                num_heads=2, out_dim=64)  # out_dim == tiny llama hidden


def _png_data_url(seed=0, size=(40, 24)) -> str:
    from PIL import Image

    rng = np.random.default_rng(seed)
    arr = rng.integers(0, 255, size=(size[1], size[0], 3), dtype=np.uint8)
    img = Image.fromarray(arr, "RGB")
    buf = io.BytesIO()
    img.save(buf, format="PNG")
    b64 = base64.b64encode(buf.getvalue()).decode()
    return f"data:image/png;base64,{b64}"


def test_processor_data_url_resize_and_expand():
    url = _png_data_url(seed=1)
    img = load_image_array(url)
    assert img.dtype == np.uint8 and img.shape == (24, 40, 3)
    px = preprocess_pixels(img, 32)
    assert px.shape == (32, 32, 3) and px.dtype == np.float32
    assert px.min() >= -1.0 and px.max() <= 1.0
    # determinism (multi-controller requirement: every host must derive
    # identical pixels)
    assert np.array_equal(px, preprocess_pixels(img, 32))
    # http is a clear error (zero-egress deployment)
    with pytest.raises(ValueError, match="data: URL"):
        load_image_array("https://example.com/cat.png")
    # placeholder expansion
    ids, start = expand_image_prompt([5, 9, 7, 3], 9, 4)
    assert ids == [5, 9, 9, 9, 9, 7, 3] and start == 1
    ids, start = expand_image_prompt([5, 7], 9, 4)
    assert ids == [5, 7] and start == -1


def test_vision_encoder_shapes_and_determinism():
    params = init_vit_params(VIT, jax.random.PRNGKey(0))
    px = np.ones((2, 32, 32, 3), np.float32) * 0.25
    out = np.asarray(encode_pixels(params, VIT, jnp.asarray(px)))
    assert out.shape == (2, VIT.num_patches, VIT.out_dim)
    out2 = np.asarray(encode_pixels(params, VIT, jnp.asarray(px)))
    assert np.array_equal(out, out2)
    # different pixels -> different embeddings
    out3 = np.asarray(
        encode_pixels(params, VIT, jnp.asarray(px * -1.0))
    )
    assert not np.allclose(out, out3)


def test_prefill_mm_matches_embedding_oracle():
    """prefill_mm == running the stack on manually spliced embeddings."""
    cfg = L.LlamaConfig.tiny(vocab_size=64)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    P, bs = 16, 4
    nb = P // bs
    kshape = (cfg.num_layers, cfg.num_kv_heads, nb + 1, bs, cfg.head_dim)
    tokens = jnp.asarray(np.arange(1, P + 1) % 60, jnp.int32)
    table = jnp.arange(1, nb + 1, dtype=jnp.int32) % (nb + 1)
    M, start = 4, 3
    mm = jnp.asarray(
        np.random.default_rng(5).normal(size=(M, cfg.hidden_size)),
        jnp.float32,
    )
    k0 = layer_caches(kshape, jnp.float32)
    v0 = layer_caches(kshape, jnp.float32)
    got, _, _ = L.prefill_mm(
        params, cfg, tokens, jnp.int32(P), k0, v0, table, mm, jnp.int32(start)
    )
    x = params["embed"][tokens].astype(params["embed"].dtype)
    x = x.at[start : start + M].set(mm.astype(x.dtype))
    want, _, _ = L._prefill_from_embeds(
        params, cfg, x, jnp.int32(P),
        layer_caches(kshape, jnp.float32), layer_caches(kshape, jnp.float32),
        table,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # and the splice actually matters: text-only logits differ
    text, _, _ = L.prefill(
        params, cfg, tokens, jnp.int32(P),
        layer_caches(kshape, jnp.float32), layer_caches(kshape, jnp.float32),
        table,
    )
    assert not np.allclose(np.asarray(got), np.asarray(text), atol=1e-3)


def test_encode_wire_codec_roundtrip_exact():
    from dynamo_tpu.multimodal.encode_worker import (
        EncodeWorker,
        decode_embeddings,
    )
    from dynamo_tpu.pipeline.context import Context

    params = init_vit_params(VIT, jax.random.PRNGKey(3))
    worker = EncodeWorker(params, VIT)
    url = _png_data_url(seed=2)
    local = worker.encode_numpy(url)

    async def roundtrip():
        async for resp in worker.handler({"image_url": url}, Context()):
            return decode_embeddings(dict(resp))

    import asyncio

    wire = asyncio.run(roundtrip())
    assert np.array_equal(local, wire)  # bit-identical over the wire


def _mm_engine(encoder):
    from dynamo_tpu.graphs.common import build_tiny_jax_engine
    from dynamo_tpu.multimodal.worker import MultimodalEngine

    engine = build_tiny_jax_engine()
    return MultimodalEngine(
        engine, encoder, placeholder_id=0, num_patches=VIT.num_patches
    )


async def _greedy_tokens(engine, token_ids, extra=None, n=8):
    from dynamo_tpu.pipeline.context import Context
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    req = PreprocessedRequest(
        token_ids=list(token_ids),
        sampling=SamplingOptions(greedy=True),
        stop=StopConditions(max_tokens=n, ignore_eos=True),
        extra=dict(extra or {}),
    )
    out = []
    async for item in engine.generate(req, Context()):
        out.extend(item.token_ids or [])
        if item.finish_reason is not None:
            break
    return out


@pytest.mark.slow
async def test_engine_serves_image_device_vs_wire_identical():
    """E2E: same image+text request through (a) the colocated DEVICE path
    (EncodeWorker in-process, embeddings via device_put) and (b) the
    disaggregated WIRE path (encode worker served over the fabric,
    embeddings wire-coded) — decoded tokens must be IDENTICAL, proving the
    encode disaggregation is lossless (the reference's claim for its NIXL
    transfer, connect/__init__.py:397)."""
    from dynamo_tpu.multimodal.encode_worker import EncodeClient, EncodeWorker
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    url = _png_data_url(seed=4)
    prompt = [5, 6, 7, 8]
    vit_params = init_vit_params(VIT, jax.random.PRNGKey(7))

    # (a) colocated device path
    dev_engine = _mm_engine(EncodeWorker(vit_params, VIT))
    dev_tokens = await _greedy_tokens(
        dev_engine, prompt, extra={"mm_images": [url]}
    )
    # no-image baseline must differ (the image actually conditions output)
    text_tokens = await _greedy_tokens(dev_engine, prompt)
    await dev_engine.close()

    # (b) wire path: encode worker behind a fabric endpoint
    drt = await DistributedRuntime.detached()
    try:
        worker = EncodeWorker(vit_params, VIT)
        svc = await worker.serve(drt, "dynamo.encoder.encode")
        client = EncodeClient(drt, "dynamo.encoder.encode")
        wire_engine = _mm_engine(client)
        wire_tokens = await _greedy_tokens(
            wire_engine, prompt, extra={"mm_images": [url]}
        )
        await wire_engine.close()
        await client.close()
        await svc.stop(drain=False)
    finally:
        await drt.close()

    assert dev_tokens == wire_tokens, (dev_tokens, wire_tokens)
    assert dev_tokens != text_tokens


async def test_image_request_rejected_on_text_only_model():
    """A model without image support must 501 an image_url part, not
    silently answer text-only."""
    import aiohttp

    from dynamo_tpu.engine.echo import EchoEngineCore
    from dynamo_tpu.entrypoint.inputs import EngineConfig, run_http
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    from tests.util import make_test_mdc

    drt = await DistributedRuntime.detached()
    service = None
    try:
        config = EngineConfig.static_(EchoEngineCore(), make_test_mdc("t"))
        service = await run_http(drt, config, host="127.0.0.1", port=0)
        payload = {
            "model": "t",
            "messages": [
                {
                    "role": "user",
                    "content": [
                        {
                            "type": "image_url",
                            "image_url": {"url": _png_data_url()},
                        },
                        {"type": "text", "text": "hello"},
                    ],
                }
            ],
        }
        async with aiohttp.ClientSession() as session:
            async with session.post(
                f"http://127.0.0.1:{service.port}/v1/chat/completions",
                json=payload,
            ) as resp:
                assert resp.status == 501
    finally:
        if service:
            await service.close()
        await drt.close()


@pytest.mark.slow
async def test_multimodal_http_e2e():
    """OpenAI image_url content part -> preprocessor extraction ->
    MultimodalEngine -> streamed completion, over a real HTTP server."""
    import aiohttp

    from dynamo_tpu.entrypoint.inputs import EngineConfig, run_http
    from dynamo_tpu.graphs.common import word_level_mdc
    from dynamo_tpu.multimodal.encode_worker import EncodeWorker
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    vit_params = init_vit_params(VIT, jax.random.PRNGKey(7))
    engine = _mm_engine(EncodeWorker(vit_params, VIT))
    drt = await DistributedRuntime.detached()
    service = None
    try:
        config = EngineConfig.static_(engine, word_level_mdc("mm-model"))
        service = await run_http(drt, config, host="127.0.0.1", port=0)
        base = f"http://127.0.0.1:{service.port}"
        payload = {
            "model": "mm-model",
            "messages": [
                {
                    "role": "user",
                    "content": [
                        {
                            "type": "image_url",
                            "image_url": {"url": _png_data_url(seed=9)},
                        },
                        {"type": "text", "text": "w1 w2 w3"},
                    ],
                }
            ],
            "max_tokens": 6,
            "temperature": 0,
        }
        async with aiohttp.ClientSession() as session:
            async with session.post(
                f"{base}/v1/chat/completions", json=payload
            ) as resp:
                assert resp.status == 200, await resp.text()
                data = await resp.json()
        content = data["choices"][0]["message"]["content"]
        assert isinstance(content, str) and content.strip()
    finally:
        if service:
            await service.close()
        await engine.close()
        await drt.close()


# ------------------------------------------------------------------ video


def _gif_data_url(n_frames=6, seed=0, size=(20, 16)) -> str:
    from PIL import Image

    rng = np.random.default_rng(seed)
    frames = [
        Image.fromarray(
            rng.integers(0, 255, size=(size[1], size[0], 3), dtype=np.uint8),
            "RGB",
        )
        for _ in range(n_frames)
    ]
    buf = io.BytesIO()
    frames[0].save(
        buf, format="GIF", save_all=True, append_images=frames[1:],
        duration=50, loop=0,
    )
    b64 = base64.b64encode(buf.getvalue()).decode()
    return f"data:image/gif;base64,{b64}"


def _mp4_file(tmp_path, n_frames=10, seed=3, size=(32, 24)):
    import cv2

    rng = np.random.default_rng(seed)
    path = str(tmp_path / "clip.mp4")
    w = cv2.VideoWriter(
        path, cv2.VideoWriter_fourcc(*"mp4v"), 10.0, size
    )
    for _ in range(n_frames):
        w.write(rng.integers(0, 255, (size[1], size[0], 3), dtype=np.uint8))
    w.release()
    return path


def test_video_frames_gif_and_sampling():
    from dynamo_tpu.multimodal.processor import (
        expand_video_prompt,
        load_video_frames,
        preprocess_video,
        sample_frames,
    )

    frames = load_video_frames(_gif_data_url(n_frames=6), num_frames=4)
    assert frames.shape == (4, 16, 20, 3) and frames.dtype == np.uint8
    # shorter clips repeat frames -> static shapes for the encoder jit
    short = load_video_frames(_gif_data_url(n_frames=2), num_frames=5)
    assert short.shape == (5, 16, 20, 3)
    # uniform sampling picks first and last frames
    stack = np.arange(10)[:, None, None, None] * np.ones(
        (1, 4, 4, 3), np.uint8
    )
    picked = sample_frames(stack.astype(np.uint8), 4)
    assert picked[0].flat[0] == 0 and picked[-1].flat[0] == 9
    px = preprocess_video(frames, 32)
    assert px.shape == (4, 32, 32, 3) and px.dtype == np.float32
    # one span of num_frames*num_patches placeholders
    ids, start = expand_video_prompt([5, 9, 7], 9, num_frames=4, num_patches=3)
    assert ids == [5] + [9] * 12 + [7] and start == 1
    with pytest.raises(ValueError, match="data: URL"):
        load_video_frames("https://example.com/cat.mp4")


def test_video_frames_mp4(tmp_path):
    from dynamo_tpu.multimodal.processor import load_video_frames

    path = _mp4_file(tmp_path)
    frames = load_video_frames(path, num_frames=8)
    assert frames.shape == (8, 24, 32, 3)
    # frames differ (the decoder is really reading the stream)
    assert not np.array_equal(frames[0], frames[-1])


def test_encode_frames_matches_per_frame_encode():
    """The batched video span must equal per-frame encodes concatenated in
    temporal order — the layout expand_video_prompt sizes the span for."""
    from dynamo_tpu.multimodal.processor import load_video_frames, preprocess_video
    from dynamo_tpu.multimodal.vision import encode_frames

    params = init_vit_params(VIT, jax.random.PRNGKey(0))
    frames = load_video_frames(_gif_data_url(n_frames=5, seed=2), 3)
    px = preprocess_video(frames, VIT.image_size)
    span = np.asarray(encode_frames(params, VIT, jnp.asarray(px)))
    P = VIT.num_patches
    assert span.shape == (3 * P, VIT.out_dim)
    for t in range(3):
        solo = np.asarray(
            encode_pixels(params, VIT, jnp.asarray(px[t : t + 1]))
        )[0]
        np.testing.assert_allclose(span[t * P : (t + 1) * P], solo, rtol=1e-6)


async def test_encode_worker_serves_video_over_wire():
    """Full video E->P handoff: worker decodes + encodes a clip, client
    receives the span over the fabric wire codec bit-exactly."""
    from dynamo_tpu.multimodal.encode_worker import EncodeClient, EncodeWorker
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    params = init_vit_params(VIT, jax.random.PRNGKey(0))
    worker = EncodeWorker(params, VIT)
    url = _gif_data_url(n_frames=6, seed=4)
    drt = await DistributedRuntime.detached()
    try:
        await worker.serve(drt, "mm.encoder.encode")
        client = EncodeClient(drt, "mm.encoder.encode")
        got = await client.encode_video(url, num_frames=4)
        want = worker.encode_video_numpy(url, num_frames=4)
        assert got.shape == (4 * VIT.num_patches, VIT.out_dim)
        np.testing.assert_array_equal(got, want)
        await client.close()
    finally:
        await drt.close()


@pytest.mark.slow
async def test_engine_serves_video_device_vs_wire_identical():
    """Same video+text request through the colocated DEVICE path and the
    disaggregated WIRE path: identical greedy tokens, and the clip really
    conditions the output (differs from text-only and from a different
    clip)."""
    from dynamo_tpu.multimodal.encode_worker import EncodeClient, EncodeWorker
    from dynamo_tpu.multimodal.worker import MultimodalEngine
    from dynamo_tpu.graphs.common import build_tiny_jax_engine
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    url = _gif_data_url(n_frames=6, seed=11)
    other = _gif_data_url(n_frames=6, seed=12)
    prompt = [5, 6, 7, 8]
    vit_params = init_vit_params(VIT, jax.random.PRNGKey(7))
    FRAMES = 3

    def mm_engine(encoder):
        return MultimodalEngine(
            build_tiny_jax_engine(), encoder, placeholder_id=0,
            num_patches=VIT.num_patches, video_frames=FRAMES,
        )

    dev_engine = mm_engine(EncodeWorker(vit_params, VIT))
    dev_tokens = await _greedy_tokens(
        dev_engine, prompt, extra={"mm_videos": [url]}
    )
    other_tokens = await _greedy_tokens(
        dev_engine, prompt, extra={"mm_videos": [other]}
    )
    text_tokens = await _greedy_tokens(dev_engine, prompt)
    await dev_engine.close()

    drt = await DistributedRuntime.detached()
    try:
        worker = EncodeWorker(vit_params, VIT)
        svc = await worker.serve(drt, "dynamo.encoder.encode")
        client = EncodeClient(drt, "dynamo.encoder.encode")
        wire_engine = mm_engine(client)
        wire_tokens = await _greedy_tokens(
            wire_engine, prompt, extra={"mm_videos": [url]}
        )
        await wire_engine.close()
        await client.close()
        await svc.stop(drain=False)
    finally:
        await drt.close()

    assert dev_tokens == wire_tokens, (dev_tokens, wire_tokens)
    assert dev_tokens != text_tokens
    assert dev_tokens != other_tokens


def test_preprocessor_lifts_video_parts():
    from dynamo_tpu.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.protocols.openai import ChatCompletionRequest

    from tests.util import make_test_mdc

    pre = OpenAIPreprocessor(make_test_mdc("t"))
    req = ChatCompletionRequest.model_validate(
        {
            "model": "t",
            "messages": [
                {
                    "role": "user",
                    "content": [
                        {
                            "type": "video_url",
                            "video_url": {"url": "file:///tmp/a.mp4"},
                        },
                        {"type": "text", "text": "w1 w2"},
                    ],
                }
            ],
        }
    )
    out, _ = pre.preprocess_chat(req)
    assert out.extra["mm_videos"] == ["file:///tmp/a.mp4"]
    assert "mm_images" not in out.extra
