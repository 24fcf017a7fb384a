"""Qwen2 model-family support: llama-shaped with q/k/v projection biases.

Covers HF-config detection, bias application in the shared _qkv head,
TP-sharded serving of biased models, and GGUF qwen2.* metadata/tensors
(reference parity: the engine zoo serves Qwen2 via vLLM; here the same
family runs on the native JAX engine)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from tests.util import layer_caches
from dynamo_tpu.models import llama as L


def qwen_cfg():
    return dataclasses.replace(L.LlamaConfig.tiny(vocab_size=64), attn_bias=True)


def test_hf_config_detection():
    cfg = L.LlamaConfig.from_hf_dict(
        {"model_type": "qwen2", "hidden_size": 64, "num_attention_heads": 4}
    )
    assert cfg.attn_bias
    cfg2 = L.LlamaConfig.from_hf_dict(
        {"architectures": ["Qwen2ForCausalLM"], "hidden_size": 64,
         "num_attention_heads": 4}
    )
    assert cfg2.attn_bias
    assert not L.LlamaConfig.from_hf_dict({"model_type": "llama"}).attn_bias


def _prefill_logits(cfg, params, toks=8):
    shape = (cfg.num_layers, cfg.num_kv_heads, 16, 4, cfg.head_dim)
    kc = layer_caches(shape, jnp.bfloat16)
    vc = layer_caches(shape, jnp.bfloat16)
    tokens = jnp.arange(toks, dtype=jnp.int32) + 2
    logits, _, _ = L.prefill(
        params, cfg, tokens, jnp.int32(toks), kc, vc,
        jnp.array([1, 2], jnp.int32),
    )
    return np.asarray(logits, np.float32)


def test_bias_is_applied():
    cfg = qwen_cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    assert "bq" in params["layers"][0]
    base = _prefill_logits(cfg, params)
    # zero biases == plain llama forward on the same weights
    plain = {
        **params,
        "layers": [
            {k: v for k, v in lyr.items() if k not in ("bq", "bk", "bv")}
            for lyr in params["layers"]
        ],
    }
    np.testing.assert_allclose(
        base, _prefill_logits(dataclasses.replace(cfg, attn_bias=False), plain),
        atol=1e-6,
    )
    # nonzero bias must change the logits
    biased = {
        **params,
        "layers": [
            {**lyr, "bq": lyr["bq"] + 0.5} for lyr in params["layers"]
        ],
    }
    assert np.abs(_prefill_logits(cfg, biased) - base).max() > 1e-3


def test_qwen2_tp_sharded_decode():
    from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner
    from dynamo_tpu.parallel.mesh import build_mesh
    from dynamo_tpu.parallel.sharding import shard_llama

    cfg = qwen_cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(1))
    mesh = build_mesh(tp=2)
    sharded, kv_sharding = shard_llama(mesh, cfg, params)
    runner = ModelRunner(
        cfg, sharded, num_blocks=16, block_size=4, max_batch=2,
        max_model_len=64, mesh=mesh, kv_sharding=kv_sharding,
    )
    out = runner.prefill([3, 5, 7, 9], block_ids=[1], temperature=0.0,
                         top_p=1.0, top_k=0)
    tok = int(np.asarray(out[0]))
    assert 0 <= tok < cfg.vocab_size
    # parity with the unsharded forward
    runner1 = ModelRunner(
        cfg, params, num_blocks=16, block_size=4, max_batch=2,
        max_model_len=64,
    )
    out1 = runner1.prefill([3, 5, 7, 9], block_ids=[1], temperature=0.0,
                           top_p=1.0, top_k=0)
    assert tok == int(np.asarray(out1[0]))


def test_gguf_qwen2_arch_with_biases(tmp_path):
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_gguf_hub import _T_F32, _T_STRING, _T_U32, write_gguf
    from dynamo_tpu.gguf import GGML_F32, GgufFile, config_from_gguf, params_from_gguf

    cfg = qwen_cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(2))
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    md = {
        "general.architecture": (_T_STRING, "qwen2"),
        "qwen2.embedding_length": (_T_U32, cfg.hidden_size),
        "qwen2.feed_forward_length": (_T_U32, cfg.intermediate_size),
        "qwen2.block_count": (_T_U32, cfg.num_layers),
        "qwen2.attention.head_count": (_T_U32, cfg.num_heads),
        "qwen2.attention.head_count_kv": (_T_U32, cfg.num_kv_heads),
        "qwen2.attention.key_length": (_T_U32, cfg.head_dim),
        "qwen2.context_length": (_T_U32, cfg.max_position_embeddings),
        "qwen2.vocab_size": (_T_U32, cfg.vocab_size),
        "qwen2.rope.freq_base": (_T_F32, cfg.rope_theta),
        "qwen2.attention.layer_norm_rms_epsilon": (_T_F32, cfg.rms_eps),
    }
    tensors = {
        "token_embd.weight": (f32(params["embed"]), GGML_F32),
        "output_norm.weight": (f32(params["final_norm"]), GGML_F32),
        "output.weight": (f32(params["lm_head"]).T, GGML_F32),
    }
    names = {
        "attn_norm": ("attn_norm.weight", False),
        "wq": ("attn_q.weight", True), "wk": ("attn_k.weight", True),
        "wv": ("attn_v.weight", True), "wo": ("attn_output.weight", True),
        "mlp_norm": ("ffn_norm.weight", False),
        "wg": ("ffn_gate.weight", True), "wu": ("ffn_up.weight", True),
        "wd": ("ffn_down.weight", True),
    }
    for i, lyr in enumerate(params["layers"]):
        for ours, (suffix, tr) in names.items():
            a = f32(lyr[ours])
            tensors[f"blk.{i}.{suffix}"] = (a.T if tr else a, GGML_F32)
        for ours, suffix in (("bq", "attn_q.bias"), ("bk", "attn_k.bias"),
                             ("bv", "attn_v.bias")):
            tensors[f"blk.{i}.{suffix}"] = (f32(lyr[ours]) + 0.25, GGML_F32)
    path = str(tmp_path / "q2.gguf")
    write_gguf(path, md, tensors)
    g = GgufFile(path)
    cfg2 = config_from_gguf(g)
    assert cfg2.attn_bias and cfg2.vocab_size == cfg.vocab_size
    cfg2, params2 = params_from_gguf(g)
    assert "bq" in params2["layers"][0]
    np.testing.assert_allclose(
        np.asarray(params2["layers"][0]["bq"], np.float32),
        f32(params["layers"][0]["bq"]) + 0.25,
        atol=1e-2,
    )
    g.close()
