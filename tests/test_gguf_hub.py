"""GGUF reader + hub model resolution (round-2 VERDICT missing #8;
ref lib/llm/src/gguf/, hub.rs:105). The test WRITES a spec-conformant GGUF
v3 file with a tiny llama's weights, then loads and serves from it."""

import json
import os
import struct

import jax
import numpy as np
import pytest

from dynamo_tpu.gguf import (
    GGML_BF16,
    GGML_F32,
    GGML_Q8_0,
    GgufFile,
    config_from_gguf,
    params_from_gguf,
)
from dynamo_tpu.hub import resolve_model
from dynamo_tpu.models import llama as L

# ------------------------------------------------------------ gguf writer

_T_U32, _T_F32, _T_STRING, _T_ARRAY, _T_U64 = 4, 6, 8, 9, 10


def _w_string(f, s):
    b = s.encode()
    f.write(struct.pack("<Q", len(b)))
    f.write(b)


def _w_kv(f, key, vtype, value):
    _w_string(f, key)
    f.write(struct.pack("<I", vtype))
    if vtype == _T_STRING:
        _w_string(f, value)
    elif vtype == _T_U32:
        f.write(struct.pack("<I", value))
    elif vtype == _T_F32:
        f.write(struct.pack("<f", value))
    elif vtype == _T_ARRAY:
        etype, items = value
        f.write(struct.pack("<IQ", etype, len(items)))
        for it in items:
            if etype == _T_STRING:
                _w_string(f, it)
            elif etype == _T_U32:
                f.write(struct.pack("<I", it))
            elif etype == _T_F32:
                f.write(struct.pack("<f", it))
            else:
                raise NotImplementedError
    else:
        raise NotImplementedError


def write_gguf(path, metadata, tensors, align=32):
    """tensors: {name: (np_array, ggml_type)} — array already in NUMPY
    row-major orientation ([out, in] for matrices, as llama.cpp stores)."""
    with open(path, "wb") as f:
        f.write(struct.pack("<IIQQ", 0x46554747, 3, len(tensors), len(metadata)))
        for key, (vtype, value) in metadata.items():
            _w_kv(f, key, vtype, value)
        blobs = []
        offset = 0
        for name, (arr, gt) in tensors.items():
            _w_string(f, name)
            dims = list(reversed(arr.shape))  # ggml order
            f.write(struct.pack("<I", len(dims)))
            for d in dims:
                f.write(struct.pack("<Q", d))
            if gt == GGML_F32:
                blob = np.ascontiguousarray(arr, np.float32).tobytes()
            elif gt == GGML_BF16:
                import ml_dtypes

                blob = (
                    np.ascontiguousarray(arr)
                    .astype(ml_dtypes.bfloat16)
                    .view(np.uint16)
                    .tobytes()
                )
            elif gt == GGML_Q8_0:
                flat = np.ascontiguousarray(arr, np.float32).reshape(-1, 32)
                d = np.abs(flat).max(axis=1) / 127.0
                d = np.where(d == 0, 1e-8, d).astype(np.float16)
                q = np.clip(
                    np.round(flat / d.astype(np.float32)[:, None]), -127, 127
                ).astype(np.int8)
                rec = np.zeros(
                    len(flat), dtype=np.dtype([("d", "<f2"), ("q", "i1", (32,))])
                )
                rec["d"] = d
                rec["q"] = q
                blob = rec.tobytes()
            else:
                raise NotImplementedError
            offset = (offset + align - 1) // align * align
            f.write(struct.pack("<IQ", gt, offset))
            blobs.append((offset, blob))
            offset += len(blob)
        pos = f.tell()
        data_start = (pos + align - 1) // align * align
        f.write(b"\x00" * (data_start - pos))
        for off, blob in blobs:
            f.seek(data_start + off)
            f.write(blob)


def tiny_cfg():
    return L.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=8, rope_theta=10000.0,
        max_position_embeddings=64,
    )


def build_gguf_from_params(path, cfg, params):
    md = {
        "general.architecture": (_T_STRING, "llama"),
        "general.alignment": (_T_U32, 32),
        "llama.embedding_length": (_T_U32, cfg.hidden_size),
        "llama.feed_forward_length": (_T_U32, cfg.intermediate_size),
        "llama.block_count": (_T_U32, cfg.num_layers),
        "llama.attention.head_count": (_T_U32, cfg.num_heads),
        "llama.attention.head_count_kv": (_T_U32, cfg.num_kv_heads),
        "llama.attention.key_length": (_T_U32, cfg.head_dim),
        "llama.context_length": (_T_U32, cfg.max_position_embeddings),
        "llama.vocab_size": (_T_U32, cfg.vocab_size),
        "llama.rope.freq_base": (_T_F32, cfg.rope_theta),
        "llama.attention.layer_norm_rms_epsilon": (_T_F32, cfg.rms_eps),
    }
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    tensors = {
        "token_embd.weight": (f32(params["embed"]), GGML_BF16),
        "output_norm.weight": (f32(params["final_norm"]), GGML_F32),
        "output.weight": (f32(params["lm_head"]).T, GGML_BF16),
    }
    names = {
        "attn_norm": ("attn_norm.weight", False, GGML_F32),
        "wq": ("attn_q.weight", True, GGML_BF16),
        "wk": ("attn_k.weight", True, GGML_BF16),
        "wv": ("attn_v.weight", True, GGML_BF16),
        "wo": ("attn_output.weight", True, GGML_BF16),
        "mlp_norm": ("ffn_norm.weight", False, GGML_F32),
        "wg": ("ffn_gate.weight", True, GGML_BF16),
        "wu": ("ffn_up.weight", True, GGML_BF16),
        "wd": ("ffn_down.weight", True, GGML_BF16),
    }
    for i, layer in enumerate(params["layers"]):
        for ours, (suffix, tr, gt) in names.items():
            a = f32(layer[ours])
            tensors[f"blk.{i}.{suffix}"] = (a.T if tr else a, gt)
    write_gguf(path, md, tensors)


def test_gguf_roundtrip_and_forward(tmp_path):
    cfg = tiny_cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    path = str(tmp_path / "tiny.gguf")
    build_gguf_from_params(path, cfg, params)

    g = GgufFile(path)
    assert g.version == 3
    cfg2 = config_from_gguf(g)
    assert cfg2.hidden_size == cfg.hidden_size
    assert cfg2.num_kv_heads == cfg.num_kv_heads
    assert cfg2.vocab_size == cfg.vocab_size
    cfg2, params2 = params_from_gguf(g)

    # weights round-trip exactly (bf16 -> bf16)
    np.testing.assert_allclose(
        np.asarray(params2["embed"], np.float32),
        np.asarray(params["embed"], np.float32),
    )
    np.testing.assert_allclose(
        np.asarray(params2["layers"][1]["wq"], np.float32),
        np.asarray(params["layers"][1]["wq"], np.float32),
    )
    # and the loaded model computes the same logits
    import jax.numpy as jnp

    from tests.util import layer_caches

    shape = (cfg.num_layers, cfg.num_kv_heads, 8, 4, cfg.head_dim)
    kc = layer_caches(shape, jnp.bfloat16)
    vc = layer_caches(shape, jnp.bfloat16)
    toks = jnp.arange(8, dtype=jnp.int32) + 2
    table = jnp.array([1, 2], jnp.int32)
    ref, _, _ = L.prefill(params, cfg, toks, jnp.int32(8), kc, vc, table)
    got, _, _ = L.prefill(
        params2, cfg2, toks, jnp.int32(8),
        layer_caches(shape, jnp.bfloat16), layer_caches(shape, jnp.bfloat16),
        table,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-2, atol=1e-2)
    g.close()


def test_gguf_q8_0_dequant(tmp_path):
    rng = np.random.default_rng(0)
    w = rng.normal(size=(16, 64)).astype(np.float32)
    path = str(tmp_path / "q.gguf")
    write_gguf(
        path,
        {"general.architecture": (_T_STRING, "llama")},
        {"w": (w, GGML_Q8_0)},
    )
    g = GgufFile(path)
    got = g.tensor("w")
    assert got.shape == w.shape
    # int8 block quantization: ~1% relative error on this scale
    np.testing.assert_allclose(got, w, atol=np.abs(w).max() / 100)
    g.close()


@pytest.mark.slow
async def test_factory_serves_from_gguf(tmp_path):
    """build_jax_engine('model.gguf') serves greedy tokens identical to the
    same weights loaded from a directory."""
    from dynamo_tpu.engine.jax_engine.factory import build_jax_engine
    from tests.test_multihost import _tiny_model_dir
    from tests.test_colocated_disagg import collect_tokens

    model_dir = _tiny_model_dir(tmp_path)
    engine_dir, _ = await build_jax_engine(
        model_dir, name="t", kv_block_size=4, max_batch=4, num_blocks=64
    )
    prompt = list(range(2, 14))
    ref = await collect_tokens(engine_dir, prompt)

    cfg = L.LlamaConfig.from_model_dir(model_dir)
    from dynamo_tpu.engine.jax_engine.weights import load_or_init_params

    params = load_or_init_params(model_dir, cfg)
    gguf_path = str(tmp_path / "tiny.gguf")
    build_gguf_from_params(gguf_path, cfg, params)
    engine_g, mdc = await build_jax_engine(
        gguf_path, kv_block_size=4, max_batch=4, num_blocks=64
    )
    assert mdc.name == "tiny"
    got = await collect_tokens(engine_g, prompt)
    assert got == ref
    await engine_dir.close()
    await engine_g.close()


def test_hub_resolution(tmp_path, monkeypatch):
    # local dir passes through
    d = tmp_path / "model"
    d.mkdir()
    assert resolve_model(str(d)) == str(d)
    # HF-cache layout resolves to the newest snapshot with a config
    cache = tmp_path / "cache"
    snap = cache / "models--org--repo" / "snapshots" / "abc123"
    snap.mkdir(parents=True)
    (snap / "config.json").write_text("{}")
    monkeypatch.setenv("DYN_MODEL_CACHE", str(cache))
    assert resolve_model("org/repo") == str(snap)
    # missing model: actionable error, no network attempt
    monkeypatch.delenv("DYN_ALLOW_DOWNLOAD", raising=False)
    with pytest.raises(FileNotFoundError, match="Pre-stage"):
        resolve_model("org/absent")


async def test_factory_serves_from_gguf_embedded_tokenizer(tmp_path):
    """A GGUF in a bare directory (no tokenizer files) serves using the
    tokenizer embedded in its own tokenizer.ggml metadata (reference
    gguf_tokenizer.rs convert_gguf_to_hf_tokenizer), and the resulting
    model card publishes/downloads that tokenizer intact."""
    from dynamo_tpu.engine.jax_engine.factory import build_jax_engine
    from dynamo_tpu.fabric.client import FabricClient
    from dynamo_tpu.fabric.state import FabricState
    from dynamo_tpu.model_card import ModelDeploymentCard
    from tests.test_colocated_disagg import collect_tokens

    cfg = tiny_cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    bare = tmp_path / "bare"
    bare.mkdir()
    path = str(bare / "tiny.gguf")
    build_gguf_from_params(path, cfg, params)

    # rewrite the file with tokenizer.ggml metadata: SP-style pieces
    # covering the model vocab (64 ids), with scores and types
    pieces = ["<unk>", "<s>", "</s>"] + [f"▁w{i}" for i in range(61)]
    types = [2, 3, 3] + [1] * 61
    scores = [0.0, 0.0, 0.0] + [-float(i) / 10 for i in range(61)]
    tensors = {}
    g1 = GgufFile(path)
    for name in g1.tensors:
        # copy: F32 tensors are views into the mmap, which must close
        tensors[name] = (np.array(g1.tensor(name)), GGML_F32)
    g1.close()
    meta = {
        "general.architecture": (_T_STRING, "llama"),
        "llama.embedding_length": (_T_U32, cfg.hidden_size),
        "llama.feed_forward_length": (_T_U32, cfg.intermediate_size),
        "llama.block_count": (_T_U32, cfg.num_layers),
        "llama.attention.head_count": (_T_U32, cfg.num_heads),
        "llama.attention.head_count_kv": (_T_U32, cfg.num_kv_heads),
        "llama.attention.key_length": (_T_U32, cfg.head_dim),
        "llama.context_length": (_T_U32, cfg.max_position_embeddings),
        "llama.vocab_size": (_T_U32, cfg.vocab_size),
        "llama.rope.freq_base": (_T_F32, cfg.rope_theta),
        "llama.attention.layer_norm_rms_epsilon": (_T_F32, cfg.rms_eps),
        "tokenizer.ggml.model": (_T_STRING, "llama"),
        "tokenizer.ggml.tokens": (_T_ARRAY, (_T_STRING, pieces)),
        "tokenizer.ggml.scores": (_T_ARRAY, (_T_F32, scores)),
        "tokenizer.ggml.token_type": (_T_ARRAY, (_T_U32, types)),
        "tokenizer.ggml.unknown_token_id": (_T_U32, 0),
        "tokenizer.ggml.bos_token_id": (_T_U32, 1),
        "tokenizer.ggml.eos_token_id": (_T_U32, 2),
    }
    write_gguf(path, meta, tensors)

    engine, mdc = await build_jax_engine(
        path, kv_block_size=4, max_batch=4, num_blocks=64
    )
    assert mdc.tokenizer_kind == "sp"
    tok = mdc.load_tokenizer()
    enc = tok.encode("w1 w2", add_special_tokens=False)
    assert tok.decode(enc.ids) == "w1 w2"
    toks = await collect_tokens(engine, list(range(2, 10)))
    assert len(toks) == 8
    await engine.close()

    # publish/download preserves the embedded tokenizer
    fabric = FabricClient.in_process(FabricState())
    await mdc.publish(fabric)
    got = await ModelDeploymentCard.download(fabric, mdc.slug)
    tok2 = got.load_tokenizer()
    assert tok2.encode("w5", add_special_tokens=False).ids == tok.encode(
        "w5", add_special_tokens=False
    ).ids
