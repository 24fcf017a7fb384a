"""Fused decode-step parity (ISSUE 9): the fused norm+QKV+rope and
attn-out+O-proj+residual pallas programs vs the unfused op chain.

Op-level identity is BIT-EXACT (the kernels replay the unfused op/dtype
sequence); whole-program (jitted llama.decode) identity is asserted
token-exact on the int8-weights path and allclose on logits everywhere
(inside one jit, XLA may re-fuse the UNFUSED side's bf16 casts). Matrix:
GQA group 1/2/4, qwen bias, int8/bf16 weights, SWA + softcap variants,
and the qk-norm fallback.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.util import layer_caches
from dynamo_tpu.models import llama as L
from dynamo_tpu.ops.basics import rope_freqs
from dynamo_tpu.ops.layers import attn_out, qkv_head
from dynamo_tpu.ops.linear import (
    fused_attn_out_residual,
    fused_qkv_rope,
)


def _cfg(num_heads=4, num_kv_heads=2, **kw):
    return dataclasses.replace(
        L.LlamaConfig.tiny(),
        num_heads=num_heads, num_kv_heads=num_kv_heads, **kw,
    )


@pytest.mark.parametrize("kv_heads", [4, 2, 1])  # GQA group 1 / 2 / 4
@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("bias", [False, True])
def test_fused_qkv_rope_bit_identical(kv_heads, quant, bias):
    cfg = _cfg(num_kv_heads=kv_heads, attn_bias=bias)
    params = L.init_params(cfg, jax.random.PRNGKey(1), quantize=quant)
    layer = params["layers"][0]
    B = 3
    x = jnp.asarray(
        np.random.default_rng(2).normal(size=(B, cfg.hidden_size)),
        jnp.bfloat16,
    )
    positions = jnp.asarray([7, 0, 31], jnp.int32)
    inv = rope_freqs(cfg.head_dim, cfg.rope_theta, None)
    q0, k0, v0 = qkv_head(x, layer, cfg, inv, positions)
    angles = positions[..., None].astype(jnp.float32) * inv
    q1, k1, v1 = fused_qkv_rope(
        x, layer["attn_norm"], layer["wq"], layer["wk"], layer["wv"],
        jnp.cos(angles), jnp.sin(angles),
        eps=cfg.rms_eps, num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        bq=layer.get("bq"), bk=layer.get("bk"), bv=layer.get("bv"),
        interpret=True,
    )
    for a, b in ((q0, q1), (k0, k1), (v0, v1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("quant", [False, True])
def test_fused_attn_out_residual_bit_identical(quant):
    cfg = _cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(3), quantize=quant)
    layer = params["layers"][0]
    B = 3
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(B, cfg.hidden_size)), jnp.bfloat16)
    attn = jnp.asarray(
        rng.normal(size=(B, cfg.num_heads, cfg.head_dim)), jnp.bfloat16
    )
    o0 = attn_out(attn, x, layer, cfg)
    o1 = fused_attn_out_residual(
        attn.reshape(B, cfg.q_dim), layer["wo"], x, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(o0), np.asarray(o1))


def _decode_once(cfg, params, fused):
    c = dataclasses.replace(cfg, fused_decode=fused)
    B, bs, nb = 3, 8, 32
    shape = (c.num_layers, c.num_kv_heads, nb, bs, c.head_dim)
    kc = layer_caches(shape, jnp.bfloat16)
    vc = layer_caches(shape, jnp.bfloat16)
    toks = jnp.asarray([5, 6, 7], jnp.int32)
    pos = jnp.asarray([10, 3, 0], jnp.int32)
    bt = jnp.tile(
        jnp.arange(1, 4, dtype=jnp.int32)[None, :], (B, 1)
    )
    rows = jnp.arange(B)
    slots = bt[rows, pos // bs] * bs + pos % bs
    import functools

    f = jax.jit(functools.partial(L.decode, params, c))
    lg, _, _ = f(toks, pos, kc, vc, bt, slots)
    return np.asarray(lg, np.float32)


@pytest.mark.parametrize(
    "variant",
    [
        {},
        {"sliding_window": 16},
        {"attn_logit_softcap": 30.0, "query_pre_attn_scalar": 144.0},
        {"attn_bias": True},
    ],
    ids=["plain", "swa", "softcap", "bias"],
)
@pytest.mark.parametrize("quant", [False, True])
def test_fused_decode_program_parity(variant, quant):
    cfg = _cfg(**variant)
    params = L.init_params(cfg, jax.random.PRNGKey(5), quantize=quant)
    a = _decode_once(cfg, params, fused=False)
    b = _decode_once(cfg, params, fused=True)
    np.testing.assert_allclose(a, b, atol=0.08, rtol=0)
    if quant:
        # the int8-weights production path: greedy choice identical
        np.testing.assert_array_equal(a.argmax(-1), b.argmax(-1))


def test_qk_norm_layers_fall_back_to_unfused():
    """Gemma3-style qk-norm layers are outside the fused heads' coverage:
    with fused_decode on they take the unfused path — outputs are
    EXACTLY the unfused program's."""
    cfg = _cfg(qk_norm=True)
    params = L.init_params(cfg, jax.random.PRNGKey(6))
    a = _decode_once(cfg, params, fused=False)
    b = _decode_once(cfg, params, fused=True)
    np.testing.assert_array_equal(a, b)


def test_fused_decode_with_int8_kv_cache():
    """Fused projections + int8-resident cache compose (the full ISSUE 9
    hot path) and stay greedy-identical to the unfused int8-KV program."""
    from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner

    cfg = L.LlamaConfig.tiny(vocab_size=64)
    params = L.init_params(cfg, jax.random.PRNGKey(0), quantize=True)

    def run(fused):
        r = ModelRunner(
            cfg, params, num_blocks=64, block_size=4, max_batch=1,
            max_model_len=64, kv_dtype="int8", fused_decode=fused,
        )
        blocks = list(range(1, 9))
        tables = np.zeros((1, r.max_blocks_per_seq), np.int32)
        tables[0, :8] = blocks
        out = r.fetch_sample(
            r.prefill(list(range(2, 12)), blocks, 0.0, 1.0, 0)
        )
        toks = [int(out[0])]
        pos = 9
        for _ in range(8):
            pos += 1
            slot = np.asarray([blocks[pos // 4] * 4 + pos % 4], np.int32)
            out = r.fetch_sample(
                r.decode(
                    np.asarray([toks[-1]], np.int32),
                    np.asarray([pos], np.int32), tables, slot,
                    np.zeros(1, np.float32), np.ones(1, np.float32),
                    np.zeros(1, np.int32),
                )
            )
            toks.append(int(out[0]))
        return toks

    assert run(False) == run(True)
