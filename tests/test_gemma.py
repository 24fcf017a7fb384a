"""Gemma (v1) model-family support: GeGLU FFN, sqrt(hidden)-scaled
embeddings, (1+w) RMSNorm weights folded at load, tied LM head.

(The reference serves Gemma through its engine zoo; here the family runs
on the native JAX engine. Gemma-2/3 soft-caps and local attention are
explicitly refused rather than silently mis-served.)"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.util import layer_caches
from dynamo_tpu.models import llama as L


def gemma_cfg():
    return dataclasses.replace(
        L.LlamaConfig.tiny(vocab_size=64),
        mlp_act="gelu_tanh", embed_scale=True, norm_plus_one=True,
        tie_word_embeddings=True,
    )


def test_hf_config_detection():
    cfg = L.LlamaConfig.from_hf_dict(
        {"model_type": "gemma", "hidden_size": 64, "num_attention_heads": 4,
         "tie_word_embeddings": True}
    )
    assert cfg.mlp_act == "gelu_tanh"
    assert cfg.embed_scale and cfg.norm_plus_one and cfg.tie_word_embeddings
    assert not cfg.sandwich_norms and not cfg.qk_norm
    plain = L.LlamaConfig.from_hf_dict({"model_type": "llama"})
    assert plain.mlp_act == "silu" and not plain.embed_scale


def test_hf_config_gemma2_and_gemma3():
    g2 = L.LlamaConfig.from_hf_dict(
        {"model_type": "gemma2", "num_hidden_layers": 4,
         "sliding_window": 4096, "attn_logit_softcapping": 50.0,
         "final_logit_softcapping": 30.0, "query_pre_attn_scalar": 256}
    )
    assert g2.sandwich_norms and not g2.qk_norm
    assert g2.attn_logit_softcap == 50.0 and g2.final_logit_softcap == 30.0
    assert g2.layer_pattern == (True, False, True, False)  # even slide
    assert g2.attn_scale == 256 ** -0.5
    g3 = L.LlamaConfig.from_hf_dict(
        {"model_type": "gemma3_text", "num_hidden_layers": 12,
         "sliding_window": 1024, "rope_theta": 1_000_000.0,
         "rope_local_base_freq": 10000.0, "query_pre_attn_scalar": 256,
         "rope_scaling": {"rope_type": "linear", "factor": 8.0}}
    )
    assert g3.sandwich_norms and g3.qk_norm
    assert g3.attn_logit_softcap is None  # gemma3 dropped soft-caps
    assert g3.rope_local_theta == 10000.0
    # 5 local : 1 global — every 6th layer is global
    assert g3.layer_pattern[:6] == (True,) * 5 + (False,)
    # explicit HF layer_types list wins over the pattern rule
    lt = L.LlamaConfig.from_hf_dict(
        {"model_type": "gemma3", "num_hidden_layers": 2,
         "sliding_window": 512,
         "layer_types": ["full_attention", "sliding_attention"]}
    )
    assert lt.layer_pattern == (False, True)


def _logits(cfg, params, toks=8):
    shape = (cfg.num_layers, cfg.num_kv_heads, 16, 4, cfg.head_dim)
    kc = layer_caches(shape, jnp.bfloat16)
    vc = layer_caches(shape, jnp.bfloat16)
    tokens = jnp.arange(toks, dtype=jnp.int32) + 2
    out, _, _ = L.prefill(
        params, cfg, tokens, jnp.int32(toks), kc, vc,
        jnp.array([1, 2], jnp.int32),
    )
    return np.asarray(out, np.float32)


def test_gemma_forward_flags_change_logits():
    cfg = gemma_cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    assert "lm_head" not in params  # tied head
    base = _logits(cfg, params)
    assert np.isfinite(base).all()
    # each family flag must actually alter the computation
    for flag in ("mlp_act", "embed_scale"):
        off = dataclasses.replace(
            cfg, **{flag: "silu" if flag == "mlp_act" else False}
        )
        assert np.abs(_logits(off, params) - base).max() > 1e-3, flag


def test_safetensors_load_folds_plus_one_norms(tmp_path):
    from safetensors.numpy import save_file

    from dynamo_tpu.engine.jax_engine.weights import load_hf_safetensors

    cfg = dataclasses.replace(gemma_cfg(), num_layers=1)
    rng = np.random.default_rng(0)
    t = {
        "model.embed_tokens.weight": rng.standard_normal(
            (cfg.vocab_size, cfg.hidden_size), dtype=np.float32
        ),
        "model.norm.weight": rng.standard_normal(
            cfg.hidden_size, dtype=np.float32
        ),
    }
    p = "model.layers.0."
    t[p + "input_layernorm.weight"] = rng.standard_normal(
        cfg.hidden_size, dtype=np.float32
    )
    t[p + "post_attention_layernorm.weight"] = rng.standard_normal(
        cfg.hidden_size, dtype=np.float32
    )
    for name, shape in (
        ("self_attn.q_proj", (cfg.q_dim, cfg.hidden_size)),
        ("self_attn.k_proj", (cfg.kv_dim, cfg.hidden_size)),
        ("self_attn.v_proj", (cfg.kv_dim, cfg.hidden_size)),
        ("self_attn.o_proj", (cfg.hidden_size, cfg.q_dim)),
        ("mlp.gate_proj", (cfg.intermediate_size, cfg.hidden_size)),
        ("mlp.up_proj", (cfg.intermediate_size, cfg.hidden_size)),
        ("mlp.down_proj", (cfg.hidden_size, cfg.intermediate_size)),
    ):
        t[p + name + ".weight"] = rng.standard_normal(shape, dtype=np.float32)
    save_file(t, str(tmp_path / "model.safetensors"))
    params = load_hf_safetensors(str(tmp_path), cfg, dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(params["final_norm"]),
        t["model.norm.weight"] + 1,
        rtol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(params["layers"][0]["attn_norm"]),
        t[p + "input_layernorm.weight"] + 1,
        rtol=1e-6,
    )
    # non-gemma configs must NOT fold
    plain = dataclasses.replace(cfg, norm_plus_one=False)
    params2 = load_hf_safetensors(str(tmp_path), plain, dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(params2["final_norm"]), t["model.norm.weight"], rtol=1e-6
    )


def test_gguf_gemma_arch(tmp_path):
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_gguf_hub import _T_F32, _T_STRING, _T_U32, write_gguf
    from dynamo_tpu.gguf import GGML_F32, GgufFile, config_from_gguf, params_from_gguf

    cfg = dataclasses.replace(gemma_cfg(), num_layers=1)
    params = L.init_params(cfg, jax.random.PRNGKey(1))
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    md = {
        "general.architecture": (_T_STRING, "gemma"),
        "gemma.embedding_length": (_T_U32, cfg.hidden_size),
        "gemma.feed_forward_length": (_T_U32, cfg.intermediate_size),
        "gemma.block_count": (_T_U32, cfg.num_layers),
        "gemma.attention.head_count": (_T_U32, cfg.num_heads),
        "gemma.attention.head_count_kv": (_T_U32, cfg.num_kv_heads),
        "gemma.attention.key_length": (_T_U32, cfg.head_dim),
        "gemma.context_length": (_T_U32, cfg.max_position_embeddings),
        "gemma.vocab_size": (_T_U32, cfg.vocab_size),
        "gemma.rope.freq_base": (_T_F32, cfg.rope_theta),
        "gemma.attention.layer_norm_rms_epsilon": (_T_F32, cfg.rms_eps),
    }
    names = {
        "attn_norm": ("attn_norm.weight", False),
        "wq": ("attn_q.weight", True), "wk": ("attn_k.weight", True),
        "wv": ("attn_v.weight", True), "wo": ("attn_output.weight", True),
        "mlp_norm": ("ffn_norm.weight", False),
        "wg": ("ffn_gate.weight", True), "wu": ("ffn_up.weight", True),
        "wd": ("ffn_down.weight", True),
    }
    tensors = {
        "token_embd.weight": (f32(params["embed"]), GGML_F32),
        "output_norm.weight": (f32(params["final_norm"]), GGML_F32),
        # no output.weight: gemma ties the LM head
    }
    for ours, (suffix, tr) in names.items():
        a = f32(params["layers"][0][ours])
        tensors[f"blk.0.{suffix}"] = (a.T if tr else a, GGML_F32)
    path = str(tmp_path / "g.gguf")
    write_gguf(path, md, tensors)
    g = GgufFile(path)
    got = config_from_gguf(g)
    assert got.mlp_act == "gelu_tanh" and got.norm_plus_one
    assert got.tie_word_embeddings
    _, params2 = params_from_gguf(g)
    assert "lm_head" not in params2
    # (1+w) fold applied to the stored norm weights
    np.testing.assert_allclose(
        np.asarray(params2["final_norm"], np.float32),
        f32(params["final_norm"]) + 1,
        atol=1e-2,
    )
    g.close()
