"""Sliding-window attention + Gemma2/3 model families.

The window is enforced by masks in the attention ops (prefill, packed,
chunked, paged decode), so Mistral-class models serve their FULL declared
context (the r4 length clamp is gone), and Gemma2/3's interleaved
local/global layers, soft-caps, sandwich norms and qk-norms are exact —
cross-checked against the canonical HF transformers implementation with
shared random weights.

(The reference serves these families through its engine zoo; here they run
on the native JAX engine — SURVEY §2 engines row.)
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.util import layer_caches
from dynamo_tpu.models import llama as L
from dynamo_tpu.ops.attention import (
    causal_prefill_attention,
    paged_decode_attention,
)


# ------------------------------------------------------------- ops level


def _np_windowed_attention(q, k, v, window):
    """Brute-force numpy reference: causal + sliding-window masked MHA."""
    P, H, D = q.shape
    out = np.zeros_like(q, dtype=np.float32)
    for h in range(H):
        scores = (q[:, h].astype(np.float32) @ k[:, h].astype(np.float32).T)
        scores /= np.sqrt(D)
        for i in range(P):
            for j in range(P):
                if j > i or (window is not None and i - j >= window):
                    scores[i, j] = -1e30
        w = np.exp(scores - scores.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        out[:, h] = w @ v[:, h].astype(np.float32)
    return out


def test_prefill_attention_window_matches_numpy():
    rng = np.random.default_rng(0)
    P, H, D, W = 10, 2, 8, 4
    q = rng.standard_normal((P, H, D), dtype=np.float32)
    k = rng.standard_normal((P, H, D), dtype=np.float32)
    v = rng.standard_normal((P, H, D), dtype=np.float32)
    got = causal_prefill_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.int32(P),
        impl="xla", window=W,
    )
    want = _np_windowed_attention(q, k, v, W)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5, rtol=1e-5)
    # window >= P degenerates to plain causal
    got_full = causal_prefill_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.int32(P),
        impl="xla", window=64,
    )
    want_full = _np_windowed_attention(q, k, v, None)
    np.testing.assert_allclose(
        np.asarray(got_full), want_full, atol=1e-5, rtol=1e-5
    )


def test_paged_decode_attention_window():
    """Decode with a window must equal decode over only the last W keys."""
    rng = np.random.default_rng(1)
    H, D, bs, W = 2, 8, 2, 4
    ctx = 9  # tokens in cache including the newest
    nb = 8
    k_cache = rng.standard_normal((H, nb, bs, D), dtype=np.float32)
    v_cache = rng.standard_normal((H, nb, bs, D), dtype=np.float32)
    table = np.array([[1, 2, 3, 4, 5]], np.int32)
    q = rng.standard_normal((1, H, D), dtype=np.float32)
    got = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
        jnp.asarray(table), jnp.array([ctx], np.int32),
        impl="xla", window=W,
    )
    # reference: flatten the pages, keep keys [ctx-W, ctx)
    flat_k = k_cache[:, table[0]].reshape(H, -1, D)[:, ctx - W:ctx]
    flat_v = v_cache[:, table[0]].reshape(H, -1, D)[:, ctx - W:ctx]
    out = np.zeros((1, H, D), np.float32)
    for h in range(H):
        s = (q[0, h] @ flat_k[h].T) / np.sqrt(D)
        w = np.exp(s - s.max())
        w /= w.sum()
        out[0, h] = w @ flat_v[h]
    np.testing.assert_allclose(np.asarray(got), out, atol=1e-5, rtol=1e-5)


# ----------------------------------------------------------- model level


def sliding_cfg(window=6, **kw):
    return dataclasses.replace(
        L.LlamaConfig.tiny(vocab_size=64), sliding_window=window, **kw
    )


def _empty_cache(cfg, num_blocks=32, block_size=4, dtype=jnp.bfloat16):
    shape = (
        cfg.num_layers, cfg.num_kv_heads, num_blocks, block_size,
        cfg.head_dim,
    )
    return layer_caches(shape, dtype), layer_caches(shape, dtype)


def _pad(a, n):
    return jnp.concatenate([a, jnp.zeros(n - a.shape[0], a.dtype)])


def _prefill_decode_consistency(cfg, T=13, K=4):
    """[prefill T + decode K] must equal one full prefill of T+K tokens —
    across the window boundary (T+K > window)."""
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    kc, vc = _empty_cache(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (T + K,), 0, 64)
    table = jnp.arange(1, 6, dtype=jnp.int32)
    logits_full, _, _ = L.prefill(
        params, cfg, _pad(toks, 20), jnp.int32(T + K), kc, vc, table
    )
    _, kc2, vc2 = L.prefill(
        params, cfg, _pad(toks[:T], 20), jnp.int32(T), kc, vc, table
    )
    bt = jnp.zeros((1, 8), jnp.int32).at[0, :5].set(table)
    logits_d = None
    for i in range(T, T + K):
        slot = table[i // 4] * 4 + i % 4
        logits_d, kc2, vc2 = L.decode(
            params, cfg, toks[i][None], jnp.array([i], jnp.int32),
            kc2, vc2, bt, slot[None],
        )
    np.testing.assert_allclose(
        np.asarray(logits_full), np.asarray(logits_d[0]),
        atol=1e-2, rtol=1e-2,
    )
    return params, toks, logits_full


def test_sliding_prefill_decode_consistency_past_window():
    cfg = sliding_cfg(window=6)
    _, _, logits_win = _prefill_decode_consistency(cfg)
    # ... and the window genuinely changes the result vs full attention
    cfg_full = dataclasses.replace(cfg, sliding_window=None)
    _, _, logits_full = _prefill_decode_consistency(cfg_full)
    assert np.abs(
        np.asarray(logits_win) - np.asarray(logits_full)
    ).max() > 1e-3


def test_sliding_chunked_prefill_matches_full():
    cfg = sliding_cfg(window=6)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    kc, vc = _empty_cache(cfg)
    T = 16
    toks = jax.random.randint(jax.random.PRNGKey(2), (T,), 0, 64)
    table = jnp.arange(1, 5, dtype=jnp.int32)
    logits_full, _, _ = L.prefill(
        params, cfg, toks, jnp.int32(T), kc, vc, table
    )
    logits_chunk = None
    kc2, vc2 = _empty_cache(cfg)
    for start in range(0, T, 8):
        logits_chunk, kc2, vc2 = L.prefill_chunk(
            params, cfg, toks[start:start + 8], jnp.int32(start),
            jnp.int32(T), kc2, vc2, table,
        )
    np.testing.assert_allclose(
        np.asarray(logits_full), np.asarray(logits_chunk),
        atol=1e-2, rtol=1e-2,
    )


def test_sliding_packed_prefill_matches_serial():
    cfg = sliding_cfg(window=4)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    bs = 4
    a = jax.random.randint(jax.random.PRNGKey(3), (7,), 0, 64)
    b = jax.random.randint(jax.random.PRNGKey(4), (6,), 0, 64)
    # serial reference
    kc, vc = _empty_cache(cfg)
    la, _, _ = L.prefill(
        params, cfg, _pad(a, 8), jnp.int32(7), kc, vc,
        jnp.array([1, 2], jnp.int32),
    )
    lb, _, _ = L.prefill(
        params, cfg, _pad(b, 8), jnp.int32(6), kc, vc,
        jnp.array([3, 4], jnp.int32),
    )
    # packed
    P = 16
    tokens = jnp.concatenate([a, b, jnp.zeros(P - 13, a.dtype)])
    positions = jnp.array(
        list(range(7)) + list(range(6)) + [0] * (P - 13), jnp.int32
    )
    seg = jnp.array([0] * 7 + [1] * 6 + [-1] * (P - 13), jnp.int32)
    slots = []
    for i in range(7):
        slots.append((1 + i // bs) * bs + i % bs)
    for i in range(6):
        slots.append((3 + i // bs) * bs + i % bs)
    slots += [0] * (P - 13)
    kc2, vc2 = _empty_cache(cfg)
    logits, _, _ = L.prefill_packed(
        params, cfg, tokens, positions, seg, jnp.array(slots, jnp.int32),
        kc2, vc2, jnp.array([6, 12], jnp.int32),
    )
    np.testing.assert_allclose(
        np.asarray(la), np.asarray(logits[0]), atol=1e-2, rtol=1e-2
    )
    np.testing.assert_allclose(
        np.asarray(lb), np.asarray(logits[1]), atol=1e-2, rtol=1e-2
    )


def gemma2_cfg(num_layers=4, window=8):
    return dataclasses.replace(
        L.LlamaConfig.tiny(vocab_size=64),
        num_layers=num_layers,
        mlp_act="gelu_tanh", embed_scale=True, norm_plus_one=True,
        tie_word_embeddings=True,
        sliding_window=window,
        layer_pattern=tuple(i % 2 == 0 for i in range(num_layers)),
        attn_logit_softcap=50.0, final_logit_softcap=30.0,
        query_pre_attn_scalar=32.0, sandwich_norms=True,
    )


def gemma3_cfg(num_layers=6, window=8):
    return dataclasses.replace(
        L.LlamaConfig.tiny(vocab_size=64),
        num_layers=num_layers,
        mlp_act="gelu_tanh", embed_scale=True, norm_plus_one=True,
        tie_word_embeddings=True,
        sliding_window=window,
        layer_pattern=tuple((i + 1) % 3 != 0 for i in range(num_layers)),
        query_pre_attn_scalar=16.0, sandwich_norms=True, qk_norm=True,
        rope_theta=1_000_000.0, rope_local_theta=10_000.0,
    )


def test_gemma2_prefill_decode_consistency():
    _prefill_decode_consistency(gemma2_cfg())


def test_gemma3_prefill_decode_consistency():
    _prefill_decode_consistency(gemma3_cfg())


def test_gemma2_feature_flags_change_logits():
    cfg = gemma2_cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    kc, vc = _empty_cache(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(5), (12,), 0, 64)
    table = jnp.array([1, 2, 3], jnp.int32)

    def logits(c):
        out, _, _ = L.prefill(
            params, c, toks, jnp.int32(12), kc, vc, table
        )
        return np.asarray(out, np.float32)

    base = logits(cfg)
    assert np.isfinite(base).all()
    # the final soft-cap bounds logits by construction
    assert np.abs(base).max() <= 30.0 + 1e-3
    for change in (
        {"attn_logit_softcap": None},
        {"query_pre_attn_scalar": None},
        {"sliding_window": None, "layer_pattern": None},
    ):
        other = logits(dataclasses.replace(cfg, **change))
        assert np.abs(other - base).max() > 1e-4, change
    # the final cap is exactly cap*tanh(raw/cap) of the uncapped logits
    # (tiny random logits sit in tanh's linear region, so compare the
    # transform, not a magnitude threshold)
    raw = logits(dataclasses.replace(cfg, final_logit_softcap=None))
    np.testing.assert_allclose(
        base, 30.0 * np.tanh(raw / 30.0), atol=1e-5, rtol=1e-5
    )


# --------------------------------------------- HF transformers golden


def _hf_round_trip(tmp_path, hf_cfg_dict, hf_model, T=12):
    """Save an HF model's weights + config, load through our stack, and
    return (our last-token logits, HF last-token logits)."""
    import torch

    ids = torch.randint(0, hf_cfg_dict["vocab_size"], (1, T))
    with torch.no_grad():
        hf_logits = hf_model(ids).logits[0, -1].float().numpy()
    from safetensors.torch import save_file

    sd = {
        k: v.detach().clone().contiguous()
        for k, v in hf_model.state_dict().items()
    }
    save_file(sd, os.path.join(tmp_path, "model.safetensors"))
    with open(os.path.join(tmp_path, "config.json"), "w") as f:
        json.dump(hf_cfg_dict, f)

    from dynamo_tpu.engine.jax_engine.weights import load_or_init_params

    cfg = L.LlamaConfig.from_model_dir(str(tmp_path))
    params = load_or_init_params(str(tmp_path), cfg, dtype=jnp.float32)
    kc, vc = _empty_cache(cfg, dtype=jnp.float32)
    toks = jnp.asarray(ids[0].numpy().astype(np.int32))
    table = jnp.arange(1, 1 + (T + 3) // 4, dtype=jnp.int32)
    ours, _, _ = L.prefill(
        params, cfg, _pad(toks, len(table) * 4), jnp.int32(T), kc, vc, table
    )
    return np.asarray(ours, np.float32), hf_logits


@pytest.mark.slow
def test_gemma2_matches_hf_transformers(tmp_path):
    torch = pytest.importorskip("torch")
    from transformers import Gemma2Config, Gemma2ForCausalLM

    hf_cfg = Gemma2Config(
        vocab_size=64, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=128, sliding_window=8,
        query_pre_attn_scalar=16.0, attn_logit_softcapping=50.0,
        final_logit_softcapping=30.0, rms_norm_eps=1e-5,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = Gemma2ForCausalLM(hf_cfg).eval()
    ours, hf = _hf_round_trip(str(tmp_path), hf_cfg.to_dict(), model)
    np.testing.assert_allclose(ours, hf, atol=2e-3, rtol=1e-3)


@pytest.mark.slow
def test_gemma3_matches_hf_transformers(tmp_path):
    torch = pytest.importorskip("torch")
    from transformers import Gemma3TextConfig
    from transformers.models.gemma3 import Gemma3ForCausalLM

    hf_cfg = Gemma3TextConfig(
        vocab_size=64, hidden_size=64, intermediate_size=128,
        num_hidden_layers=6, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=128, sliding_window=8,
        sliding_window_pattern=3, query_pre_attn_scalar=16.0,
        rope_theta=1_000_000.0, rope_local_base_freq=10_000.0,
        rms_norm_eps=1e-5, attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = Gemma3ForCausalLM(hf_cfg).eval()
    ours, hf = _hf_round_trip(str(tmp_path), hf_cfg.to_dict(), model)
    np.testing.assert_allclose(ours, hf, atol=2e-3, rtol=1e-3)


def test_mistral_style_full_depth_window_consistency():
    """Mistral: every layer slides, context well past the window."""
    cfg = sliding_cfg(window=5)
    _prefill_decode_consistency(cfg, T=17, K=3)
